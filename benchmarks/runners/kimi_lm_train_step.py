"""``kind: kimi_lm_train_step`` — next-token pretraining of a Kimi Linear
decoder (KDA and latent attention beside routed experts) through
``static.TrainStep`` on one chip, as ``lm_train_step`` trains the hybrid
decoder: the selection bias fitted at set-up, the comparison with the
plain reference (``lm_train_step.check_parity``, the configuration's
limits), batches fed from the host every step, the loss and the step's
routing counters fetched every ``loss_fetch_every`` steps."""

from __future__ import annotations

import gc
import statistics
import time
from typing import Any, Dict

import numpy as np

from .. import arithmetic, generator, kimi_lm_arithmetic, lm_generator
from ..harness import Run, log
from ..window import measure
from .lm_train_step import check_parity, fit_router_bias, make_step

COUNTERS = ("moe_pairs_held", "moe_load_max_over_mean",
            "moe_pairs_dropped", "moe_windows_run")


def build_model(run: Run):
    """The model as the configuration file states it, weights from the
    seed, parameters in the training dtype."""
    import paddle_tpu as pt
    from paddle_tpu.models import KimiLinearConfig, KimiLinearForCausalLM

    train = run.config["train"]
    pt.seed(generator.small_seed(run.seed, "weights"))
    model = KimiLinearForCausalLM(KimiLinearConfig(
        **run.config["model"], recompute=train["recompute"]))
    model.to(dtype=train["param_dtype"])
    return model


def make_batches(run: Run):
    cfg = run.config["model"]
    return lm_generator.next_token_batches(
        run.mix, cfg["vocab_size"], int(run.mix["batch_per_chip"])
        * run.chips, run.seed)


def parity_batch(run: Run, batches):
    """The sequences the comparison is made on: the first of each of the
    first ``parity_sequences`` pool batches (a loss in bf16 lies on a
    grid of 0.0625 near 10, so one sequence may read a lower-precision
    loss inside the limit; PERF.md section 6, PR 29)."""
    n = int(run.mix.get("parity_sequences", 1))
    return tuple(np.concatenate([b[i][:1] for b in batches[:n]])
                 for i in range(2))


def run(run: Run) -> Dict[str, Any]:
    import paddle_tpu as pt

    from paddle_tpu import observability as obs

    mix, cfg = run.mix, run.config["model"]
    # what an earlier run in this process (a sweep's last seed) traced
    # is dropped, and with it the step whose state the tracker keeps
    # alive, which the comparison below needs the room of
    obs.recompile_tracker().reset()
    gc.collect()
    # metrics on: the skip-step guard's counter is fed only then
    pt.set_flags({"enable_metrics": True})
    batch = int(mix["batch_per_chip"]) * run.chips
    seq, every = int(mix["seq_len"]), int(mix["loss_fetch_every"])
    t0 = time.perf_counter()
    model = build_model(run)
    batches = make_batches(run)
    log(f"model and {len(batches)} host batches of {batch} x {seq} "
        f"built in {time.perf_counter() - t0:.1f}s")
    fit_router_bias(run, model, batches[0][0])
    t0 = time.perf_counter()
    check_parity(run, model, parity_batch(run, batches))
    gc.collect()          # the comparison's arrays leave the chip
    log(f"parity check took {time.perf_counter() - t0:.1f}s")
    step = make_step(run, model)

    n_calls = 0

    def step_once():
        nonlocal n_calls
        ids, labels = batches[n_calls % len(batches)]
        n_calls += 1
        return step(ids, labels=(labels,))

    def fetch(metrics):
        return {k: float(metrics[k]) for k in ("loss",) + COUNTERS}

    observed = measure(run, step, step_once, fetch, every, batch * seq)
    fetched = observed["fetched"]
    dropped = sum(f["moe_pairs_dropped"] for f in fetched)
    run.check(dropped == 0, f"no (token, expert) pair dropped in "
              f"{len(fetched)} fetched steps ({dropped})")
    held = statistics.mean(f["moe_pairs_held"] for f in fetched)
    load = statistics.mean(f["moe_load_max_over_mean"] for f in fetched)
    windows = sorted({int(f["moe_windows_run"]) for f in fetched})
    walls = observed["spans"]["train.group_wall_ms"] or [float("nan")]
    # what a sweep keeps of a seed beside its checks' margins
    run.margins.update(
        routing_pairs_held=held, routing_load_max_over_mean=load,
        routing_windows_run_max=windows[-1],
        group_wall_ms_max_over_median=max(walls) / statistics.median(walls))
    flops = kimi_lm_arithmetic.kimi_flops_per_step(cfg, batch, seq, held)
    observed["counters"].update(
        moe_pairs_held_per_step=held, moe_load_max_over_mean=load,
        trace_model_flops=flops * every)
    expert_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    balanced = batch * seq * cfg["num_experts_per_token"] * expert_layers \
        * cfg["num_experts"] / cfg["num_experts_total"]
    log(f"routing: {held:.0f} pairs on held experts a step (balanced: "
        f"{balanced:.0f}) in {windows} windows, fullest held expert over "
        f"their mean {load:.2f}; slowest group of {every} steps "
        f"{max(walls):.1f} ms, median {statistics.median(walls):.1f}")
    if not run.rehearsal:
        kind = run.devices[0].device_kind
        tokens_per_s = observed["end_to_end"]["train_tokens_per_s"]
        share = arithmetic.mfu(tokens_per_s, flops / (batch * seq),
                               run.chips, kind)
        log(f"train_tokens_per_s {tokens_per_s:.1f}; model FLOPs a step "
            f"{flops:.4e}; MFU {100 * share:.2f}% of the bf16 peak of "
            f"{kind!r}")
    pt.set_flags({"enable_metrics": False})
    return observed
