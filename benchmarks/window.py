"""The measured window of a training cell, shared by the runner kinds
that time a train step (``lm_train_step``, ``sharded_train_step``):
warm-up, the window with its traced slice, and the checks every
training cell makes on what the window saw."""

from __future__ import annotations

import statistics
import time
from typing import Any, Callable, Dict, List

import numpy as np

from .harness import Run, log, read_trace, start_trace
from .runners.train_step import warm_up


def measure(run: Run, step, step_once: Callable[[], Dict[str, Any]],
            fetch: Callable[[Dict[str, Any]], Dict[str, float]],
            every: int, tokens_per_step: int) -> Dict[str, Any]:
    """Warm ``step_once`` up, run the window (one group of ``every``
    steps traced when the run asks for a trace), and apply the checks
    every training cell shares: finite losses, no skipped step, no
    retrace, and the loss's fall over a fixed horizon of fetches.
    ``fetch`` turns a step's metrics into host numbers (``loss`` among
    them), which ends the group. Returns what the readers take, with
    every fetch under ``fetched``."""
    import jax

    from paddle_tpu import observability as obs

    times = warm_up(lambda: step_once()["loss"])
    tracker = obs.recompile_tracker().get(step._span_name)
    traces_warm = tracker.traces
    log("warm-up calls: " + " ".join(f"{t:.3f}" for t in times)
        + f" s; traces so far {traces_warm}")

    fetched: List[Dict[str, float]] = []
    group_ms: List[float] = []
    trace_dir = run.scratch("trace") if run.trace else None
    traced = False
    w0 = run.window_starts()
    steps = 0
    while True:
        tracing = run.trace and not traced and len(fetched) >= 1
        if tracing:
            start_trace(trace_dir)
        g0 = time.perf_counter()
        if tracing:
            with jax.profiler.TraceAnnotation("bench/slice"):
                for _ in range(every):
                    with jax.profiler.TraceAnnotation("bench/step_call"):
                        metrics = step_once()
                with jax.profiler.TraceAnnotation("bench/loss_fetch"):
                    fetched.append(fetch(metrics))
        else:
            for _ in range(every):
                metrics = step_once()
            fetched.append(fetch(metrics))
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
    losses = [f["loss"] for f in fetched]
    tokens_per_s = steps * tokens_per_step / window
    log(f"window: {steps} steps in {window:.3f}s, {len(losses)} loss "
        f"fetches: " + " ".join(f"{x:.3f}" for x in losses))

    jax.effects_barrier()
    if hasattr(step, "flush_signals"):
        step.flush_signals()
    skipped = obs.counter("nonfinite_steps_total").total()
    run.check(bool(np.all(np.isfinite(losses))), "every fetched loss is "
              "finite")
    run.check(skipped == 0, f"nonfinite_steps_total == 0 ({skipped})")
    run.check(tracker.traces == traces_warm,
              f"no trace of the step after warm-up ({tracker.traces} "
              f"== {traces_warm})")
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
        "counters": {"steps_per_group": every, "trace_steps": every,
                     "chips": run.chips},
        "margins": run.margins, "fetched": fetched,
    }
    if run.trace:
        observed.update(read_trace(trace_dir))
    return observed
