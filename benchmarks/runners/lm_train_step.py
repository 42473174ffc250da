"""``kind: lm_train_step`` — next-token pretraining of a causal language
model through ``static.TrainStep`` on one chip: batches fed from the
host every step; the loss and the step's routing counters fetched every
``loss_fetch_every`` steps."""

from __future__ import annotations

import gc
import statistics
import time
from typing import Any, Dict

import numpy as np

from .. import arithmetic, lm_arithmetic, lm_generator, generator
from ..harness import Run, log
from ..manifest import bench_module
from ..window import measure

COUNTERS = ("moe_pairs_held", "moe_load_max_over_mean",
            "moe_pairs_dropped")


def build_model(run: Run):
    """The model as the configuration file states it, weights from the
    seed, parameters in the training dtype."""
    import paddle_tpu as pt
    from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM

    train = run.config["train"]
    pt.seed(generator.small_seed(run.seed, "weights"))
    model = NemotronHForCausalLM(NemotronHConfig(
        **run.config["model"], recompute=train["recompute"]))
    model.to(dtype=train["param_dtype"])
    return model


def fit_router_bias(run: Run, model, input_ids) -> None:
    """The selection bias of every expert layer fitted to the first
    batch by the source's own balancing rule (the configuration file
    gives the reason under ``assumed``)."""
    from paddle_tpu.models import balance_router_bias

    t0 = time.perf_counter()
    worst = balance_router_bias(model, input_ids)
    log(f"router bias fitted in {time.perf_counter() - t0:.1f}s: fullest "
        f"expert over the mean {worst:.2f} (largest over the layers)")


def make_step(run: Run, model):
    import paddle_tpu as pt
    from paddle_tpu.models import next_token_loss, routing_metrics
    from paddle_tpu.static import TrainStep

    train = run.config["train"]
    opt = pt.optimizer.AdamW(train["learning_rate"],
                             weight_decay=train["weight_decay"])
    return TrainStep(model, opt, next_token_loss,
                     extra_metrics=routing_metrics(),
                     seed=generator.small_seed(run.seed, "dropout"))


def timed_path(model, leaves):
    """The system's side of the comparison: loss, the gradients of
    ``leaves`` and the drop counter from the model as the step runs it
    (training mode, recomputation as configured)."""
    import jax

    from paddle_tpu.models import next_token_loss
    from paddle_tpu.nn.layer import functional_call

    def system(p, b, ids, labels):
        def loss_of(q):
            out = functional_call(model, q, b, ids)
            return next_token_loss(out, labels), out
        (loss, out), g = jax.value_and_grad(loss_of, has_aux=True)(p)
        return loss, {k: g[k] for k in leaves}, out.moe_pairs_dropped
    return system


def check_parity(run: Run, model, batch, system=None) -> None:
    """The system's loss and watched gradients against the plain float32
    reference, at the published widths, on every sequence of the batch,
    one sequence of the timed length at a time (the same two programs
    run again), BEFORE the optimizer state exists (the reference's
    float32 copy of the parameters would not fit beside it). A leaf's
    error is taken over the sequences together (the norm of all the
    differences over the norm of all the reference's gradients), the
    loss's is the largest of them. Why every sequence: a loss computed
    in bfloat16 lies on a grid of 0.0625 near 10, so its distance from
    the float32 loss is anything from 0 to 0.031 and falls inside the
    limit on one sequence in ten (PERF.md section 6, PR 29). The system
    side is the timed path (``timed_path``) unless ``system`` gives
    another function of the same signature:
    ``benchmarks/control_drill.py`` holds the comparison's limits
    against the reference computed one precision down."""
    import jax
    import jax.numpy as jnp

    tol = run.config["tolerances"]
    cfg = run.config["model"]
    limits = dict(tol["grad_rel_l2"])       # a limit for every leaf
    leaves = list(limits)
    params, buffers = model.param_dict(), model.buffer_dict()
    # parameters and the sample are ARGUMENTS of both programs (see
    # runners/train_step.py: a constant would be in the cache's key)
    system = jax.jit(system or timed_path(model, leaves))

    ref = bench_module("references", run.cell["config"])

    @jax.jit
    @jax.value_and_grad
    def reference(watched, p, b, ids, labels):
        return ref.loss({**p, **watched}, cfg, ids, labels, b)

    off, size = dict.fromkeys(leaves, 0.0), dict.fromkeys(leaves, 0.0)
    loss_abs, losses, dropped = 0.0, [], 0
    for row in range(len(batch[0])):
        ids, labels = (np.asarray(a[row:row + 1]) for a in batch)
        sys_loss, sys_g, drops = system(params, buffers, ids, labels)
        sys_loss, dropped = float(sys_loss), dropped + int(drops)
        sys_g = {k: np.asarray(v, np.float32) for k, v in sys_g.items()}
        watched = {k: params[k].astype(jnp.float32) for k in leaves}
        ref_loss, ref_g = reference(watched, params, buffers, ids, labels)
        ref_loss = float(ref_loss)
        for k in leaves:
            b = np.asarray(ref_g[k], np.float32)
            off[k] += float(np.sum(np.square(sys_g[k] - b)))
            size[k] += float(np.sum(np.square(b)))
        del ref_g, watched, sys_g
        loss_abs = max(loss_abs, abs(sys_loss - ref_loss))
        losses.append(f"{sys_loss:.5f} vs {ref_loss:.5f}")
    over = []
    for k in leaves:
        rel = (off[k] / size[k]) ** 0.5
        run.margins["parity_grad_rel:" + k] = rel
        if not rel <= limits[k]:
            over.append(k)
        log(f"parity grad {k}: rel l2 error {rel:.5f} <= {limits[k]} "
            f"(|ref|={size[k] ** 0.5:.4e})")
    run.margins["parity_loss_abs"] = loss_abs
    run.check(loss_abs <= tol["loss_abs"],
              f"parity loss: system vs reference {', '.join(losses)}, "
              f"largest |diff| {loss_abs:.5f} <= {tol['loss_abs']}")
    run.check(not over, f"parity gradients of {len(leaves)} leaves, each "
              f"within its limit (rel l2 error over it: {over})")
    run.check(dropped == 0, f"parity: no pair dropped ({dropped})")


def run(run: Run) -> Dict[str, Any]:
    import paddle_tpu as pt

    from paddle_tpu import observability as obs

    mix, cfg = run.mix, run.config["model"]
    # what an earlier run in this process (a sweep's last seed) traced
    # is dropped, and with it the step whose state the tracker keeps
    # alive: 9.3 GB that the comparison below needs
    obs.recompile_tracker().reset()
    gc.collect()
    # metrics on: the skip-step guard's counter is fed only then
    pt.set_flags({"enable_metrics": True})
    batch = int(mix["batch_per_chip"]) * run.chips
    seq, every = int(mix["seq_len"]), int(mix["loss_fetch_every"])
    t0 = time.perf_counter()
    model = build_model(run)
    batches = lm_generator.next_token_batches(mix, cfg["vocab_size"],
                                              batch, run.seed)
    log(f"model and {len(batches)} host batches of {batch} x {seq} "
        f"built in {time.perf_counter() - t0:.1f}s")
    fit_router_bias(run, model, batches[0][0])
    t0 = time.perf_counter()
    check_parity(run, model, batches[0])
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
    flops = lm_arithmetic.nemotron_h_flops_per_step(cfg, batch, seq, held)
    observed["counters"].update(
        moe_pairs_held_per_step=held, moe_load_max_over_mean=load,
        trace_model_flops=flops * every)
    balanced = batch * seq * cfg["num_experts_per_tok"] \
        * cfg["hybrid_override_pattern"].count("E") \
        * cfg["n_routed_experts"] / cfg["n_routed_experts_total"]
    log(f"routing: {held:.0f} pairs on held experts a step (balanced: "
        f"{balanced:.0f}), fullest held expert over their mean {load:.2f}")
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
