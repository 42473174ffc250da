"""``kind: bd_train_step`` — block-diffusion training of a routed-experts
decoder through ``static.TrainStep`` on one chip: every sequence run as
its noised copy followed by its clean copy under the block-diffusion
mask, batches (noise included) fed from the host every step; the loss,
the step's routing counters and its count of loss positions fetched
every ``loss_fetch_every`` steps."""

from __future__ import annotations

import gc
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from .. import arithmetic, bd_generator, bd_lm_arithmetic, generator
from ..harness import Run, log
from ..manifest import bench_module
from ..window import measure

COUNTERS = ("moe_pairs_held", "moe_load_max_over_mean",
            "moe_pairs_dropped", "moe_windows_run", "bd_masked_tokens")


def build_model(run: Run):
    """The model as the configuration file states it, weights from the
    seed, parameters in the training dtype. The embedding table is the
    model's own seeded draw scaled to ``train.embedding_table_std``
    (``assumed.initial_weights`` in the configuration file says why it
    is not the matrices' scale)."""
    import paddle_tpu as pt
    from paddle_tpu.models import SdarMoeConfig, SdarMoeForCausalLM

    train = run.config["train"]
    pt.seed(generator.small_seed(run.seed, "weights"))
    model = SdarMoeForCausalLM(SdarMoeConfig(
        **run.config["model"], recompute=train["recompute"]))
    table = model.embed_tokens.weight
    model.set_state_dict({"embed_tokens.weight": table * (
        train["embedding_table_std"] / model.config.initializer_range)},
        strict=False)
    model.to(dtype=train["param_dtype"])
    return model


def make_batches(run: Run):
    cfg = run.config["model"]
    return bd_generator.block_diffusion_batches(
        run.mix, cfg["vocab_size"], cfg["mask_token_id"],
        cfg["block_length"], int(run.mix["batch_per_chip"]) * run.chips,
        run.seed)


def fit_router_bias(run: Run, model, input_ids) -> None:
    """The selection bias of every layer's router fitted to the first
    batch (the configuration file gives the reason under ``assumed``)."""
    from paddle_tpu.models import balance_router_bias

    t0 = time.perf_counter()
    worst = balance_router_bias(model, input_ids)
    log(f"router bias fitted in {time.perf_counter() - t0:.1f}s: fullest "
        f"expert over the mean {worst:.2f} (largest over the layers)")


def make_step(run: Run, model):
    import paddle_tpu as pt
    from paddle_tpu.models import (block_diffusion_loss,
                                   block_diffusion_metrics)
    from paddle_tpu.static import TrainStep

    train = run.config["train"]
    opt = pt.optimizer.AdamW(train["learning_rate"],
                             weight_decay=train["weight_decay"])
    return TrainStep(model, opt, block_diffusion_loss,
                     extra_metrics=block_diffusion_metrics(),
                     seed=generator.small_seed(run.seed, "dropout"))


def timed_path(model, leaves):
    """The system's side of the comparison: loss, the gradients of
    ``leaves`` and the drop counter from the model as the step runs it
    (training mode, recomputation as configured)."""
    import jax

    from paddle_tpu.models import block_diffusion_loss
    from paddle_tpu.nn.layer import functional_call

    def system(p, b, ids, labels, t):
        def loss_of(q):
            out = functional_call(model, q, b, ids)
            return block_diffusion_loss(out, labels, t), out
        (loss, out), g = jax.value_and_grad(loss_of, has_aux=True)(p)
        return loss, {k: g[k] for k in leaves}, out.moe_pairs_dropped
    return system


def watched(limits: Dict[str, float], mask_row: int):
    """``{limit's key: (parameter, rows compared)}``: a key is a
    parameter's name, or ``<name>:mask_row`` for the one row of it that
    the MASK token reads."""
    out = {}
    for key in limits:
        name, _, part = key.partition(":")
        out[key] = (name, slice(mask_row, mask_row + 1)
                    if part == "mask_row" else slice(None))
    return out


class _LeafErrors:
    """The relative L2 error of each limited leaf, taken over the
    sequences together."""

    def __init__(self, limits: Dict[str, float], mask_row: int) -> None:
        self.limits = limits
        self.rows_of = watched(limits, mask_row)
        self.off = dict.fromkeys(limits, 0.0)
        self.size = dict.fromkeys(limits, 0.0)

    def kept(self, grads, scale: float = 1.0) -> Dict[str, np.ndarray]:
        """The compared rows of ``grads`` on the host, float32."""
        return {key: scale * np.asarray(grads[name], np.float32)[rows]
                for key, (name, rows) in self.rows_of.items()}

    def add(self, sys_g, ref_kept) -> None:
        for key, got in self.kept(sys_g).items():
            self.off[key] += float(np.sum(np.square(got - ref_kept[key])))
            self.size[key] += float(np.sum(np.square(ref_kept[key])))

    def judge(self, run: Run, margin: str, what: str) -> None:
        over = []
        for k, limit in self.limits.items():
            # a leaf that no loss position reaches (a rehearsal's few
            # tokens) has no gradient on either side
            if self.size[k]:
                rel = (self.off[k] / self.size[k]) ** 0.5
            else:
                rel = float("inf") if self.off[k] else 0.0
            run.margins[margin + k] = rel
            if not rel <= limit:
                over.append(k)
            log(f"{what} {k}: rel l2 error {rel:.5f} <= {limit} "
                f"(|ref|={self.size[k] ** 0.5:.4e})")
        run.check(not over, f"{what}: {len(self.limits)} leaves, each "
                  f"within its limit (rel l2 error over it: {over})")


def _unbiased(buffers):
    """``buffers`` with every router's selection bias at zero, as the
    published model has it: what the comparison over the first blocks is
    made with on both sides. It reads the mask; the fitted bias sets
    experts' scores a hair apart, and the (position, expert) pairs that
    bf16 then chooses otherwise would only blur it."""
    import jax.numpy as jnp

    return {k: jnp.zeros_like(v) if k.endswith(".e_score_correction_bias")
            else v for k, v in buffers.items()}


def _first_blocks(run: Run, length: int) -> int:
    """Tokens of a sequence in the blocks that the second comparison
    keeps (``tolerances.first_blocks``)."""
    return min(length, int(run.config["tolerances"]["first_blocks"]["blocks"])
               * int(run.config["model"]["block_length"]))


def reference_side(run: Run, model, batch) -> List[Dict[str, Any]]:
    """For every sequence of ``batch``, from the plain float32
    reference at the published widths: the loss and the compared rows
    of the watched gradients (``whole``), and the same rows for the
    loss over the first blocks alone (``first``). Blocks are causal to
    one another, so the reference computes the latter from the first
    blocks of both copies and nothing else (a test holds that equal to
    the whole sequence with every later position's weight at zero); it
    divides by the tokens it was given, the system by the sequence's,
    hence the scale."""
    import jax
    import jax.numpy as jnp

    tol, cfg = run.config["tolerances"], run.config["model"]
    whole = _LeafErrors(tol["grad_rel_l2"], cfg["mask_token_id"])
    first = _LeafErrors(tol["first_blocks"]["grad_rel_l2"],
                        cfg["mask_token_id"])
    leaves = watched_leaves(run)
    params, buffers = model.param_dict(), model.buffer_dict()
    ref = bench_module("references", run.cell["config"])

    # parameters and the sample are ARGUMENTS of both programs (see
    # runners/train_step.py: a constant would be in the cache's key)
    @jax.jit
    @jax.value_and_grad
    def reference(float_leaves, p, b, ids, labels, t):
        return ref.loss({**p, **float_leaves}, cfg, ids, labels, t, b)

    out = []
    for row in range(len(batch[0])):
        ids, labels, t = (np.asarray(a[row:row + 1]) for a in batch)
        length = labels.shape[1]
        n = _first_blocks(run, length)
        float_leaves = {k: params[k].astype(jnp.float32) for k in leaves}
        loss, g = reference(float_leaves, params, buffers, ids, labels, t)
        side = {"loss": float(loss), "whole": whole.kept(g)}
        del g
        cut = np.concatenate([ids[:, :n], ids[:, length:length + n]], 1)
        _, g = reference(float_leaves, params, _unbiased(buffers), cut,
                         labels[:, :n], t[:, :n])
        side["first"] = first.kept(g, n / length)
        del g, float_leaves
        out.append(side)
    return out


def watched_leaves(run: Run) -> List[str]:
    tol = run.config["tolerances"]
    keys = list(tol["grad_rel_l2"]) + list(
        tol["first_blocks"]["grad_rel_l2"])
    return sorted({key.partition(":")[0] for key in keys})


def check_parity(run: Run, model, batch, system=None,
                 reference=None) -> None:
    """The system's loss and watched gradients against the plain float32
    reference, at the published widths, on every sequence of the batch,
    one sequence of the timed length (twice that in positions) at a
    time, BEFORE the optimizer state exists, as ``lm_train_step``'s
    comparison does and for its reasons: a leaf's error is taken over
    the sequences together, the loss's is the largest of them.

    Then the same gradients once more for the loss over the sequence's
    FIRST BLOCKS alone (``tolerances.first_blocks``), the system run at
    the timed length with every later position's ``t`` infinite, so
    that it weighs nothing, and with the selection bias at zero on both
    sides (``_unbiased``). Over thousands of keys a query's softmax
    hardly notices four keys more or fewer, so the whole sequence's
    gradients cannot tell the block-diffusion rule from a causal mask;
    where a query has 4 to 64 keys they can.

    The system side is the timed path (``timed_path``) unless ``system``
    gives another function of the same signature; ``reference`` is
    ``reference_side``'s result where the caller has it already
    (``benchmarks/bd_control_drill.py`` uses both)."""
    import jax

    tol = run.config["tolerances"]
    cfg = run.config["model"]
    whole = _LeafErrors(tol["grad_rel_l2"], cfg["mask_token_id"])
    first = _LeafErrors(tol["first_blocks"]["grad_rel_l2"],
                        cfg["mask_token_id"])
    params, buffers = model.param_dict(), model.buffer_dict()
    system = jax.jit(system or timed_path(model, watched_leaves(run)))
    reference = reference or reference_side(run, model, batch)

    loss_abs, losses, dropped = 0.0, [], 0
    for row, ref in enumerate(reference):
        ids, labels, t = (np.asarray(a[row:row + 1]) for a in batch)
        sys_loss, sys_g, drops = system(params, buffers, ids, labels, t)
        sys_loss, dropped = float(sys_loss), dropped + int(drops)
        whole.add(sys_g, ref["whole"])
        far = np.where(np.arange(t.shape[1]) < _first_blocks(
            run, t.shape[1]), t, np.float32(np.inf)).astype(t.dtype)
        _, sys_g, drops = system(params, _unbiased(buffers), ids, labels,
                                 far)
        dropped += int(drops)
        first.add(sys_g, ref["first"])
        del sys_g
        loss_abs = max(loss_abs, abs(sys_loss - ref["loss"]))
        losses.append(f"{sys_loss:.5f} vs {ref['loss']:.5f}")
    run.margins["parity_loss_abs"] = loss_abs
    run.check(loss_abs <= tol["loss_abs"],
              f"parity loss: system vs reference {', '.join(losses)}, "
              f"largest |diff| {loss_abs:.5f} <= {tol['loss_abs']}")
    whole.judge(run, "parity_grad_rel:", "parity gradient")
    first.judge(run, "parity_first_blocks_grad_rel:",
                "parity gradient over the first blocks")
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
    log(f"model and {len(batches)} host batches of {batch} x {seq} tokens "
        f"({2 * seq} positions a sequence) built in "
        f"{time.perf_counter() - t0:.1f}s")
    fit_router_bias(run, model, batches[0][0])
    t0 = time.perf_counter()
    check_parity(run, model, batches[0])
    gc.collect()          # the comparison's arrays leave the chip
    log(f"parity check took {time.perf_counter() - t0:.1f}s")
    step = make_step(run, model)

    n_calls = 0

    def step_once():
        nonlocal n_calls
        ids, labels, t = batches[n_calls % len(batches)]
        n_calls += 1
        return step(ids, labels=(labels, t))

    def fetch(metrics):
        return {k: float(metrics[k]) for k in ("loss",) + COUNTERS}

    observed = measure(run, step, step_once, fetch, every, batch * seq)
    fetched = observed["fetched"]
    dropped = sum(f["moe_pairs_dropped"] for f in fetched)
    run.check(dropped == 0, f"no (position, expert) pair dropped in "
              f"{len(fetched)} fetched steps ({dropped})")
    held = statistics.mean(f["moe_pairs_held"] for f in fetched)
    load = statistics.mean(f["moe_load_max_over_mean"] for f in fetched)
    masked = statistics.mean(f["bd_masked_tokens"] for f in fetched)
    windows = sorted({int(f["moe_windows_run"]) for f in fetched})
    # the untraced groups' walls: a seed's slowest against its median
    walls = observed["spans"]["train.group_wall_ms"] or [float("nan")]
    # what a sweep keeps of a seed beside its checks' margins
    run.margins.update(
        routing_pairs_held=held, routing_load_max_over_mean=load,
        routing_windows_run_max=windows[-1],
        group_wall_ms_max_over_median=max(walls) / statistics.median(walls))
    flops = bd_lm_arithmetic.sdar_flops_per_step(cfg, batch, seq, held)
    observed["counters"].update(
        moe_pairs_held_per_step=held, moe_load_max_over_mean=load,
        bd_masked_share=masked / (batch * seq),
        trace_model_flops=flops * every)
    balanced = 2 * batch * seq * cfg["num_experts_per_tok"] \
        * cfg["num_hidden_layers"] * cfg["num_experts"] \
        / cfg["num_experts_total"]
    log(f"routing: {held:.0f} pairs on held experts a step (balanced: "
        f"{balanced:.0f}) in {windows} windows, fullest held expert over "
        f"their mean {load:.2f}; {masked:.0f} of {batch * seq} tokens "
        f"masked a step; slowest group of {every} steps {max(walls):.1f} ms, "
        f"median {statistics.median(walls):.1f}")
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
