"""Recompilation tracker for jit entry points.

jax retraces (and XLA recompiles) a jitted function for every new
abstract input signature; in the reference that cost shows up as
ProgramDesc re-construction + pass re-runs, here it is the dominant
silent perf cliff (ROADMAP: "as fast as the hardware allows" dies to a
shape-churning input pipeline). This module wraps the framework's jit
boundaries (jit.StaticFunction, static.TrainStep/EvalStep) to

- count traces vs. cache hits per function,
- record per-trace compile latency (wall time of the dispatch call that
  traced) and the triggering abstract input signature, and
- warn ONCE per function on a recompilation storm: ≥
  FLAGS_recompile_warn_threshold distinct signatures.

Mechanics: ``FunctionRecord.mark_trace(fn)`` wraps the to-be-jitted
function so its body — which only executes while jax is tracing —
notes the trace; ``wrap_call`` wraps the jitted callable to time
dispatches and classify each call as hit or trace via a thread-local
handoff (tracing runs synchronously on the calling thread). Trace
notes are always on (they cost only at compile time); per-call
hit/latency accounting is gated on FLAGS_enable_metrics.
"""

from __future__ import annotations

import contextlib
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

from . import metrics as _metrics
from . import tracer as _tracer
from . import xprof as _xprof

__all__ = ["RecompileTracker", "FunctionRecord", "tracker",
           "instrumented_jit"]


def _abstract_signature(args, kwargs) -> str:
    """Stable string of every leaf's (shape, dtype) — leaves are
    tracers at trace time, concrete arrays on eager fallback."""
    import jax

    def leaf_sig(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None:
            return repr(type(x).__name__)
        return f"{getattr(dtype, 'name', dtype)}{list(shape)}"

    leaves = jax.tree.leaves((args, kwargs))
    return "(" + ",".join(leaf_sig(x) for x in leaves) + ")"


class FunctionRecord:
    """Per-function trace/call accounting."""

    def __init__(self, name: str, lock: threading.Lock) -> None:
        self.name = name
        self._lock = lock
        self._tls = threading.local()
        self.traces = 0
        self.calls = 0
        self.hits = 0
        self.signatures: List[str] = []
        self.compile_times_s: List[float] = []
        self._warned = False
        # what the newest trace under metrics left to lower from again
        # (xprof.op_scopes): abstract (args, kwargs) and — once the
        # dispatch that traced has returned — the mesh in scope, its
        # arguments' shardings and the jitted callable. The callable
        # keeps its owner (a TrainStep and its state) alive until the
        # next trace under this name, metrics going off at a trace, or
        # reset(): whoever asks may come after the owner was dropped.
        self._kept: Optional[Dict[str, Any]] = None

    # -- trace side --------------------------------------------------------

    def note_trace(self, args, kwargs) -> None:
        if getattr(self._tls, "suppress", False):
            # an xprof harvest re-traces through .lower(); that trace is
            # bookkeeping, not user-visible recompilation
            return
        sig = _abstract_signature(args, kwargs)
        if _metrics.enabled():
            # Capture the abstract signature as ShapeDtypeStructs while
            # the tracers are live: after a donated-argnum dispatch the
            # concrete args are deleted, so this is the only safe point
            # to keep a lowerable description (for the program-card
            # harvest and for xprof.op_scopes).
            import jax

            def to_sds(x):
                shape = getattr(x, "shape", None)
                dtype = getattr(x, "dtype", None)
                if shape is None or dtype is None:
                    return x
                weak = bool(getattr(getattr(x, "aval", None),
                                    "weak_type", False))
                return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                            weak_type=weak)

            try:
                kept = {"avals": jax.tree.map(to_sds, (args, kwargs)),
                        "signature": sig}
            except Exception:  # noqa: BLE001 — analytics never break a trace
                kept = None
            with self._lock:
                self._kept = kept
        else:
            with self._lock:
                self._kept = None
        threshold = None
        with self._lock:
            self.traces += 1
            if sig not in self.signatures:
                self.signatures.append(sig)
            n_sigs = len(self.signatures)
            if not self._warned:
                threshold = self._threshold()
                if threshold and n_sigs >= threshold:
                    self._warned = True
                else:
                    threshold = None
        self._tls.traced = True
        # the step timeline's pt/host/compile event of this trace:
        # begun here, where tracing starts, ended by on_call
        self._tls.compile_event = _tracer.tracer().host_event_begin(
            _tracer.COMPILE_EVENT, what="trace", of=self.name)
        _metrics.counter(
            "jit_traces_total",
            "jit traces (recompilations) per function", always=True
        ).inc(fn=self.name)
        # flight recorder: recompiles are prime crash/efficiency
        # forensics (a storm right before OOM tells the whole story)
        from . import flight as _flight
        _flight.record("recompile", fn=self.name, signature=sig[:200],
                       distinct_signatures=n_sigs)
        if threshold:
            warnings.warn(
                f"recompilation storm: '{self.name}' has been traced "
                f"for {n_sigs} distinct input signatures (threshold "
                f"{threshold}); latest {sig[:200]} — pad or bucket "
                f"input shapes (FLAGS_recompile_warn_threshold)",
                RuntimeWarning, stacklevel=3)

    @staticmethod
    def _threshold() -> int:
        try:
            from ..flags import GLOBAL_FLAGS
            return int(GLOBAL_FLAGS.get("recompile_warn_threshold"))
        except Exception:
            return 0

    def mark_trace(self, fn: Callable) -> Callable:
        """Wrap ``fn`` (pre-jit) so tracing it is observed."""
        def traced(*args, **kwargs):
            self.note_trace(args, kwargs)
            # kernels traced inside note their work under this name
            with _xprof.tracing(self.name):
                return fn(*args, **kwargs)
        traced.__name__ = getattr(fn, "__name__", "fn")
        traced.__qualname__ = getattr(fn, "__qualname__", traced.__name__)
        traced.__wrapped__ = fn
        return traced

    # -- call side ---------------------------------------------------------

    def keep_call(self, jitted: "_InstrumentedJit", args, kwargs) -> None:
        """After a dispatch that traced: remember who to lower again
        and where its arguments lived (a batch committed to a mesh
        carries its sharding into jit; an abstract value must be told
        it, or the program lowered again is not the one that ran)."""
        import jax
        with self._lock:
            kept = self._kept
        if kept is None:
            return
        kept["jitted"] = jitted
        # the caller's set_mesh is still in scope here (get_mesh cannot
        # be asked inside the trace)
        kept["mesh"] = jax.sharding.get_mesh()

        def placed(aval, x):
            sharding = getattr(x, "sharding", None) \
                if isinstance(x, jax.Array) else None
            # on one device there is nothing to tell, and telling it
            # would change the persistent cache's key: the program
            # lowered again must load, not compile
            if sharding is None or len(sharding.device_set) == 1 or \
                    not isinstance(aval, jax.ShapeDtypeStruct):
                return aval
            return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                        sharding=sharding,
                                        weak_type=aval.weak_type)

        try:
            kept["avals"] = jax.tree.map(placed, kept["avals"],
                                         (args, kwargs))
        # ptlint: disable=silent-failure -- a pytree that cannot be walked beside its own abstract copy keeps the unplaced signature; analytics never break a step
        except ValueError:
            pass

    @contextlib.contextmanager
    def lowering_again(self):
        """What the newest trace kept — ``None`` when metrics were off
        at it, or the dispatch that traced failed — with the mesh that
        was in scope then set again and the trace that a second
        lowering causes not counted."""
        import jax
        with self._lock:
            kept = self._kept
        if kept is None or "jitted" not in kept:
            yield None
            return
        scope = contextlib.nullcontext() if kept["mesh"].empty \
            else jax.sharding.set_mesh(kept["mesh"])
        self._tls.suppress = True
        try:
            with scope:
                yield kept
        finally:
            self._tls.suppress = False

    def compile_again(self):
        """Lower and compile the entry point from what its newest trace
        kept. ``None`` when nothing was kept or metrics are off now: a
        step's body reads that flag while it is traced, so lowering it
        again with the flag off could give another program than the
        one that ran."""
        if not _metrics.enabled():
            return None
        with self.lowering_again() as kept:
            if kept is None:
                return None
            args, kwargs = kept["avals"]
            return kept["jitted"].lower(*args, **kwargs).compile()

    def on_call(self, t0_ns: int) -> bool:
        """Classify the dispatch that began at ``t0_ns``
        (``time.perf_counter_ns``) and has just returned; returns True
        when it traced. Only then is the clock read again."""
        traced = getattr(self._tls, "traced", False)
        self._tls.traced = False
        dt_s = 0.0
        if traced:
            dt_s = (time.perf_counter_ns() - t0_ns) / 1e9
            _tracer.tracer().host_event_end(
                getattr(self._tls, "compile_event", None))
            self._tls.compile_event = None
        with self._lock:
            self.calls += 1
            if traced:
                self.compile_times_s.append(dt_s)
            else:
                self.hits += 1
        if traced:
            _metrics.histogram(
                "jit_compile_seconds",
                "wall time of dispatch calls that traced",
                buckets=(0.01, 0.05, 0.1, 0.5, 1, 5, 15, 60, 300)
            ).observe(dt_s, fn=self.name)
        else:
            _metrics.counter("jit_cache_hits_total",
                             "jit dispatches served from cache"
                             ).inc(fn=self.name)
        return traced

    def wrap_call(self, jitted: Callable) -> "_InstrumentedJit":
        return _InstrumentedJit(jitted, self)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"name": self.name, "traces": self.traces,
                    "calls": self.calls, "hits": self.hits,
                    "signatures": list(self.signatures),
                    "compile_times_s": list(self.compile_times_s)}


class _InstrumentedJit:
    """Callable wrapper that times dispatches; every other attribute
    (``lower``, ``clear_cache``, ...) passes through to the jitted fn."""

    def __init__(self, jitted: Callable, record: FunctionRecord) -> None:
        object.__setattr__(self, "_jitted", jitted)
        object.__setattr__(self, "_record", record)

    def __call__(self, *args, **kwargs):
        rec: FunctionRecord = self._record
        if not _metrics.enabled():
            # still consume a pending trace marker so a later enabled
            # call is not misclassified as a compile
            rec._tls.traced = False
            return self._jitted(*args, **kwargs)
        # inside a train entry point's dispatch phase the call is
        # timed from that phase's own stamp
        t0 = _tracer.tracer().dispatch_began_ns()
        if t0 is None:
            t0 = time.perf_counter_ns()
        out = self._jitted(*args, **kwargs)
        traced = rec.on_call(t0)
        if traced:
            rec.keep_call(self, args, kwargs)
            self._maybe_harvest(rec)
        return out

    def _maybe_harvest(self, rec: "FunctionRecord") -> None:
        """Program-card harvest for the trace that just completed. Runs
        lower().compile() over the kept ShapeDtypeStructs (no data,
        donation-safe)."""
        if not _xprof.enabled():
            return
        with rec.lowering_again() as kept:
            if kept is not None:
                _xprof.harvest(rec.name, self._jitted, *kept["avals"],
                               kept["signature"])

    def __getattr__(self, item):
        return getattr(self._jitted, item)


class RecompileTracker:
    """Registry of FunctionRecords, keyed by display name."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._fns: Dict[str, FunctionRecord] = {}

    def function(self, name: str) -> FunctionRecord:
        with self._lock:
            rec = self._fns.get(name)
            if rec is None:
                rec = FunctionRecord(name, threading.Lock())
                self._fns[name] = rec
            return rec

    def get(self, name: str) -> Optional[FunctionRecord]:
        with self._lock:
            return self._fns.get(name)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            fns = list(self._fns.values())
        return {r.name: r.stats() for r in fns}

    def reset(self) -> None:
        with self._lock:
            self._fns.clear()


_TRACKER = RecompileTracker()


def tracker() -> RecompileTracker:
    return _TRACKER


def instrumented_jit(fn: Callable, name: Optional[str] = None,
                     **jit_kwargs) -> _InstrumentedJit:
    """``jax.jit`` with recompile tracking: drop-in at jit boundaries.

    Returns a callable; ``.lower()`` etc. still work (attribute
    passthrough).
    """
    import jax
    name = name or getattr(fn, "__qualname__",
                           getattr(fn, "__name__", repr(fn)))
    rec = _TRACKER.function(name)
    return rec.wrap_call(jax.jit(rec.mark_trace(fn), **jit_kwargs))
