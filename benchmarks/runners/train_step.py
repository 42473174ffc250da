"""``kind: train_step`` — BERT pretraining through ``static.TrainStep``
on one chip: batches fed from the host every step, the loss fetched
every ``loss_fetch_every`` steps as a logging trainer does."""

from __future__ import annotations

import statistics
import time
from typing import Any, Dict, List

import numpy as np

from .. import arithmetic, generator
from ..harness import Run, log, read_trace, start_trace
from ..manifest import bench_module


def build_model(run: Run):
    """The model and optimizer as the configuration file states them,
    weights from the seed."""
    import paddle_tpu as pt
    from paddle_tpu.models import BertConfig, BertForPretraining

    train = run.config["train"]
    pt.seed(generator.small_seed(run.seed, "weights"))
    model = BertForPretraining(BertConfig(**run.config["model"]))
    model.to(dtype=train["param_dtype"])
    opt = pt.optimizer.AdamW(train["learning_rate"],
                             weight_decay=train["weight_decay"])
    return model, opt


def make_step(run: Run, model, opt):
    from paddle_tpu.models import pretraining_loss
    from paddle_tpu.static import TrainStep
    return TrainStep(model, opt, pretraining_loss,
                     seed=generator.small_seed(run.seed, "dropout"))


def check_parity(run: Run, step, model, batch) -> None:
    """(a) The system's loss and a few gradients on one sequence
    against the plain float32 reference, dropout off, published widths,
    before the window. Tolerances are the configuration file's, with
    their reasons."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import pretraining_loss
    from paddle_tpu.nn.layer import functional_call

    tol = run.config["tolerances"]
    cfg = run.config["model"]
    last = cfg["num_hidden_layers"] - 1
    leaves = [n.replace("{last}", str(last)) for n in tol["grad_leaves"]]
    sample = tuple(np.asarray(a[:1]) for a in batch)
    params, buffers = step.state["params"], step.state["buffers"]

    # Everything that differs from run to run — parameters, the sample —
    # is an ARGUMENT of the two programs, never a constant inside them:
    # a constant is part of the persistent cache's key, so a program
    # that closed over the seed's arrays would compile afresh in every
    # run and leave a new entry of the parameters' size in the cache.
    def system(p, ids, pos, mlm, nsp):
        def loss_of(q):
            out = functional_call(model, q, buffers, ids,
                                  masked_positions=pos)
            return pretraining_loss(out, mlm, nsp).astype(jnp.float32)
        loss, g = jax.value_and_grad(loss_of)(p)
        return loss, {k: g[k] for k in leaves}

    ref = bench_module("references", run.cell["config"])

    def reference(watched, p, ids, pos, mlm, nsp):
        return ref.loss({**p, **watched}, cfg, ids, pos, mlm, nsp)

    model.eval()
    try:
        sys_loss, sys_g = jax.jit(system)(params, *sample)
        watched = {k: params[k].astype(jnp.float32) for k in leaves}
        ref_loss, ref_g = jax.jit(jax.value_and_grad(reference))(
            watched, params, *sample)
    finally:
        model.train()
    sys_loss, ref_loss = float(sys_loss), float(ref_loss)
    worst = 0.0
    for k in leaves:
        a = np.asarray(sys_g[k], np.float32)
        b = np.asarray(ref_g[k], np.float32)
        rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        worst = max(worst, rel)
        log(f"parity grad {k}: rel l2 error {rel:.5f} "
            f"(|ref|={np.linalg.norm(b):.4e})")
    run.margins.update(parity_loss_abs=abs(sys_loss - ref_loss),
                       parity_grad_rel=worst)
    run.check(abs(sys_loss - ref_loss) <= tol["loss_abs"],
              f"parity loss: system {sys_loss:.5f} vs reference "
              f"{ref_loss:.5f}, |diff| {abs(sys_loss - ref_loss):.5f} "
              f"<= {tol['loss_abs']}")
    run.check(worst <= tol["grad_rel_l2"],
              f"parity gradients of {len(leaves)} leaves: worst rel l2 "
              f"error {worst:.5f} <= {tol['grad_rel_l2']}")


def warm_up(step_once, max_calls: int = 12) -> List[float]:
    """Call until two consecutive calls are fast (a cache-loaded
    executable can re-lay-out on a later call), each ended by the loss.
    Returns the wall time of each call."""
    times: List[float] = []
    for _ in range(max_calls):
        t0 = time.perf_counter()
        float(step_once())
        times.append(time.perf_counter() - t0)
        if len(times) >= 3 and max(times[-2:]) <= 1.5 * min(times):
            break
    return times


def run(run: Run) -> Dict[str, Any]:
    import jax

    import paddle_tpu as pt
    from paddle_tpu import observability as obs

    mix, cfg = run.mix, run.config["model"]
    # metrics on: the skip-step guard's counter is fed only then
    pt.set_flags({"enable_metrics": True})
    batch = int(mix["batch_per_chip"]) * run.chips
    seq, every = int(mix["seq"]), int(mix["loss_fetch_every"])
    t0 = time.perf_counter()
    model, opt = build_model(run)
    step = make_step(run, model, opt)
    batches = generator.pretraining_batches(mix, cfg["vocab_size"], batch,
                                          run.seed)
    log(f"model, step and {len(batches)} host batches of "
        f"{batch} x {seq} built in {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    check_parity(run, step, model, batches[0])
    log(f"parity check took {time.perf_counter() - t0:.1f}s")

    n_calls = 0

    def step_once():
        nonlocal n_calls
        ids, pos, mlm, nsp = batches[n_calls % len(batches)]
        n_calls += 1
        return step(ids, labels=(mlm, nsp), masked_positions=pos)["loss"]

    times = warm_up(step_once)
    tracker = obs.recompile_tracker().get(step._span_name)
    traces_warm = tracker.traces
    log("warm-up calls: " + " ".join(f"{t:.3f}" for t in times)
        + f" s; traces so far {traces_warm}")

    flops = arithmetic.bert_flops_per_token(cfg, seq,
                                            int(mix["predicted"]))
    losses: List[float] = []
    group_ms: List[float] = []
    trace_dir = run.scratch("trace") if run.trace else None
    traced = False
    w0 = run.window_starts()
    steps = 0
    while True:
        tracing = (run.trace and not traced and len(losses) >= 1)
        if tracing:
            start_trace(trace_dir)
        g0 = time.perf_counter()
        if tracing:
            with jax.profiler.TraceAnnotation("bench/slice"):
                for _ in range(every):
                    with jax.profiler.TraceAnnotation("bench/step_call"):
                        loss = step_once()
                with jax.profiler.TraceAnnotation("bench/loss_fetch"):
                    losses.append(float(loss))
        else:
            for _ in range(every):
                loss = step_once()
            losses.append(float(loss))
        now = time.perf_counter()
        if tracing:
            jax.profiler.stop_trace()
            traced = True
        else:
            group_ms.append((now - g0) * 1e3)
        steps += every
        if now - w0 >= run.seconds and (traced or not run.trace):
            break
    window = now - w0
    tokens_per_s = steps * batch * seq / window
    log(f"window: {steps} steps in {window:.3f}s, {len(losses)} loss "
        f"fetches: " + " ".join(f"{x:.3f}" for x in losses))

    jax.effects_barrier()
    step.flush_signals()
    skipped = obs.counter("nonfinite_steps_total").total()
    kind = run.devices[0].device_kind
    if not run.rehearsal:
        share = arithmetic.mfu(tokens_per_s, flops, run.chips, kind)
        log(f"train_tokens_per_s {tokens_per_s:.1f} over {run.chips} "
            f"chip(s); model_flops_per_token {flops:.0f}; MFU "
            f"{100 * share:.2f}% of {run.chips} x bf16 peak of {kind!r}")
    # (b) nothing non-finite, nothing skipped, nothing retraced
    run.check(bool(np.all(np.isfinite(losses))), "every fetched loss is "
              "finite")
    run.check(skipped == 0, f"nonfinite_steps_total == 0 ({skipped})")
    run.check(tracker.traces == traces_warm,
              f"no trace of the step after warm-up ({tracker.traces} "
              f"== {traces_warm})")
    # (c) the loss fell, by a margin the seed sweep set
    # over a fixed horizon of fetches, so that neither the window's
    # length nor the step's speed decides it
    tol = run.config["tolerances"]
    horizon = int(tol["loss_fall_fetches"])
    if len(losses) >= 4:
        upto = min(len(losses), horizon)
        fall = statistics.mean(losses[:2]) \
            - statistics.mean(losses[upto - 2:upto])
        need = tol["loss_fall"] * (upto - 2) / (horizon - 2)
        run.margins["loss_fall"] = fall
        run.margins["loss_fall_fetches"] = upto
        run.check(fall >= need,
                  f"loss fell: mean of fetches 1-2 minus mean of "
                  f"fetches {upto - 1}-{upto} = {fall:.4f} >= {need:.4f}")
    else:
        log(f"only {len(losses)} loss fetches: the fall is not judged "
            "(a window at run_seconds holds many more)")

    observed: Dict[str, Any] = {
        "attempted": steps, "failed": int(skipped),
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "spans": {"train.group_wall_ms": group_ms},
        "counters": {"steps_per_group": every, "trace_steps": every},
        "margins": run.margins,
    }
    if run.trace:
        observed.update(read_trace(trace_dir))
    pt.set_flags({"enable_metrics": False})
    return observed
