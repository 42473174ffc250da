"""``kind: smallthinker_lm_train_step`` — next-token pretraining of a
SmallThinker decoder (windowed and global attention, a router that reads
the layer's input before attention, ReGLU experts) through
``static.TrainStep`` on one chip, as ``kimi_lm_train_step`` trains Kimi
Linear: the comparison with the plain reference on the first sequence of
``parity_sequences`` pool batches (``lm_train_step.check_parity``, the
configuration's limits), batches fed from the host every step, the loss
and the step's routing counters fetched every ``loss_fetch_every``
steps. The embedding table is scaled and the selection bias fitted, as
the configuration says, and then the timed state is compared
(``check_parity``)."""

from __future__ import annotations

import contextlib
import gc
import statistics
import time
from typing import Any, Dict

import numpy as np

from .. import arithmetic, generator, smallthinker_lm_arithmetic
from ..harness import Run, log
from ..manifest import bench_module
from ..window import measure
from .kimi_lm_train_step import COUNTERS, make_batches, parity_batch
from .lm_train_step import fit_router_bias, make_step

__all__ = ["build_model", "check_parity", "make_batches", "parity_batch",
           "run", "timed_path"]


def build_model(run: Run):
    """The model as the configuration file states it, weights from the
    seed, parameters in the training dtype. The embedding table is the
    model's own seeded draw scaled to ``train.embedding_table_std``
    (``assumed.initial_weights`` in the configuration file says why it
    is not the matrices' scale)."""
    import paddle_tpu as pt
    from paddle_tpu.models import SmallThinkerConfig, SmallThinkerForCausalLM

    train = run.config["train"]
    pt.seed(generator.small_seed(run.seed, "weights"))
    model = SmallThinkerForCausalLM(SmallThinkerConfig(
        **run.config["model"], recompute=train["recompute"]))
    table = model.embed_tokens.weight
    model.set_state_dict({"embed_tokens.weight": table * (
        train["embedding_table_std"] / model.config.initializer_range)},
        strict=False)
    model.to(dtype=train["param_dtype"])
    return model


@contextlib.contextmanager
def _handing_out(picks, recomputed: bool):
    """Every expert layer's chosen experts appended to ``picks`` as the
    pass that computes the gradients chose them: ``nn.DroplessMoE`` hands
    them out beside its counters, which leave a recomputed layer
    (``nn.recompute_layer``, ``recomputed``) as its outputs."""
    from paddle_tpu import nn
    from paddle_tpu.nn.layers.moe import DroplessMoE

    route, forward, recompute = DroplessMoE.route, DroplessMoE.forward, \
        nn.recompute_layer
    handed = {}

    def routed(self, tokens):
        chosen, w = route(self, tokens)
        handed[id(self)] = chosen
        return chosen, w

    def forwarded(self, x, router_input=None):
        out, stats = forward(self, x, router_input)
        stats = dict(stats, chosen=handed.pop(id(self)))
        if not recomputed:
            picks.append(stats["chosen"])
        return out, stats

    def recomputing(layer):
        inner = recompute(layer)

        def call(*args):
            out, stats = inner(*args)
            picks.append(stats["chosen"])
            return out, stats
        return call

    DroplessMoE.route, DroplessMoE.forward = routed, forwarded
    nn.recompute_layer = recomputing
    try:
        yield
    finally:
        DroplessMoE.route, DroplessMoE.forward = route, forward
        nn.recompute_layer = recompute


def timed_path(model, leaves):
    """The system's side of ``check_parity``: the loss, the gradients of
    ``leaves``, the drop counter, every layer's pairs per expert and the
    experts every layer chose [layers, B * L, k], all from the model as
    the step runs it (training mode, recomputation as configured), in
    one pass."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import next_token_loss
    from paddle_tpu.nn.layer import functional_call

    def system(p, b, ids, labels):
        def loss_of(q):
            picks = []
            with _handing_out(picks, model.config.recompute == "layer"
                              and model.training):
                out = functional_call(model, q, b, ids)
            return next_token_loss(out, labels), (out, jnp.stack(picks))
        (loss, (out, chosen)), g = jax.value_and_grad(loss_of,
                                                      has_aux=True)(p)
        return (loss, {k: g[k] for k in leaves}, out.moe_pairs_dropped,
                out.moe_expert_load, chosen)
    return system


def check_parity(run: Run, model, batch, system=None) -> None:
    """The system's loss and watched gradients against the plain float32
    reference in the state the step is timed in (the selection bias
    fitted, on both sides), at the published widths, one sequence of the
    batch at a time, before the optimizer state exists, as
    ``lm_train_step.check_parity`` compares; a leaf's error is taken over
    the sequences together, the loss's is the largest of them.

    The reference sends every token to the experts the system chose
    (``references.<config>.loss_and_routing``) and says how far that
    choice lies from its own: the share of (token, expert) pairs outside
    its own top-k and the largest shortfall of such a pair's score below
    its k-th, each held to its limit. A choice is discrete: where the
    fitted bias leaves a frequent token's k-th and (k+1)-th experts a
    hair apart, bfloat16 and float32 choose otherwise for every position
    of that token at once, and a gradient compared across two choices
    measures the choice, not the arithmetic (PERF.md section 6). The
    choice handed over is held to the pass's own counters of pairs per
    expert.

    ``system`` (the timed path by default, ``timed_path``) maps
    (parameters, buffers, ids, labels) to (loss, the watched gradients,
    pairs dropped, pairs per expert [layers, experts], the experts chosen
    [layers, B * L, k]); ``benchmarks/smallthinker_control_drill.py``
    plants its faults through it."""
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
    def reference(watched, p, b, ids, labels, chosen):
        return jax.value_and_grad(
            lambda w: ref.loss_and_routing({**p, **w}, cfg, ids, labels, b,
                                           chosen), has_aux=True)(watched)

    layers, scored = cfg["num_hidden_layers"], \
        cfg["moe_num_primary_experts_total"]
    off, size = dict.fromkeys(leaves, 0.0), dict.fromkeys(leaves, 0.0)
    loss_abs, losses, dropped, miscounted = 0.0, [], 0, 0
    otherwise, pairs, short = np.zeros(layers, np.int64), 0, 0.0
    for row in range(len(batch[0])):
        ids, labels = (np.asarray(a[row:row + 1]) for a in batch)
        sys_loss, sys_g, drops, loads, chosen = system(params, buffers, ids,
                                                       labels)
        sys_loss, dropped = float(sys_loss), dropped + int(drops)
        sys_g = {k: np.asarray(v, np.float32) for k, v in sys_g.items()}
        chosen = np.asarray(chosen, np.int32)
        counted = np.stack([np.bincount(c.reshape(-1), minlength=scored)
                            for c in chosen])
        miscounted += int(np.sum(np.abs(counted - np.asarray(loads))))
        watched = {k: params[k].astype(jnp.float32) for k in leaves}
        (ref_loss, (n, gap, _)), ref_g = reference(
            watched, params, buffers, ids, labels,
            jnp.asarray(chosen.reshape(layers, 1, -1, chosen.shape[-1])))
        ref_loss = float(ref_loss)
        otherwise += np.asarray(n, np.int64)
        pairs += chosen[0].size
        short = max(short, float(np.max(gap)))
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
    share = float(otherwise.max()) / pairs
    run.margins.update(parity_loss_abs=loss_abs,
                       parity_routing_otherwise_share=share,
                       parity_routing_shortfall=short)
    run.check(loss_abs <= tol["loss_abs"],
              f"parity loss: system vs reference {', '.join(losses)}, "
              f"largest |diff| {loss_abs:.5f} <= {tol['loss_abs']}")
    run.check(not over, f"parity gradients of {len(leaves)} leaves, each "
              f"within its limit (rel l2 error over it: {over})")
    run.check(share <= tol["routing_otherwise_share"],
              f"parity routing: pairs the reference would choose otherwise, "
              f"a layer {otherwise.tolist()} of {pairs}; the most "
              f"{share:.6f} <= {tol['routing_otherwise_share']}")
    run.check(short <= tol["routing_shortfall"],
              f"parity routing: largest shortfall of a chosen score below "
              f"the reference's k-th {short:.3e} <= "
              f"{tol['routing_shortfall']}")
    run.check(miscounted == 0, f"parity: the choice handed over has the "
              f"pass's pairs per expert (off by {miscounted})")
    run.check(dropped == 0, f"parity: no pair dropped ({dropped})")


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
    flops = smallthinker_lm_arithmetic.smallthinker_flops_per_step(
        cfg, batch, seq, held)
    observed["counters"].update(
        moe_pairs_held_per_step=held, moe_load_max_over_mean=load,
        trace_model_flops=flops * every)
    balanced = batch * seq * cfg["moe_num_active_primary_experts"] \
        * cfg["num_hidden_layers"] * cfg["moe_num_primary_experts"] \
        / cfg["moe_num_primary_experts_total"]
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
