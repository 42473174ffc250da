"""Build-integration paths (ref: /root/reference/python/paddle/
sysconfig.py get_include/get_lib — where extension authors find the
native headers and shared library).

Here the native surface is the C API in csrc/ptnative.h and the
auto-built libptnative.so in the native package; extensions link
against those the same way reference extensions link
libpaddle_framework.
"""

from __future__ import annotations

import os
import threading
import time

__all__ = ["get_include", "get_lib", "enable_compile_cache",
           "apply_compile_cache_flag", "compile_cache_stats"]


def get_include() -> str:
    """Directory containing ptnative.h: the source checkout's csrc/ when
    present, else the header copy the native build stages inside the
    package (installed wheels ship no csrc/ — same split native
    _needs_build handles for the .so)."""
    from .native import _CSRC
    if os.path.isdir(_CSRC):
        return _CSRC
    pkg_inc = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "include")
    if os.path.isdir(pkg_inc):
        return pkg_inc
    raise FileNotFoundError(
        "no native headers found (csrc/ missing and no packaged "
        "include/); reinstall with sources or run native.build()")


def get_lib() -> str:
    """Directory containing libptnative.so (built on first use)."""
    from . import native
    native.build()
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "native")


_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache(cache_dir: str = None,
                         min_compile_secs: float = 0.5) -> None:
    """Enable JAX's persistent compilation cache. The ONE
    implementation — verify, conftest, chip_smoke, the benchmark and
    the tools all call this, so the path and the min-compile threshold
    can't drift between entry points. Safe to call repeatedly.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the directory is the
    environment's (jax reads it itself) and no directory is set in
    code, whatever ``cache_dir`` says: whoever runs the process decides
    where its cache survives. Otherwise it is ``cache_dir``, by default
    the fixed ``<checkout>/.jax_cache`` — the path is part of the
    cache key, so it never comes from tempfile, a pid or the clock."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          cache_dir or _REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    # tiny CPU executables (tests, the self-test drill) are below
    # the default entry-size floor — persist everything; dedup is
    # the cache key's job
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _install_cache_listener()


# --------------------------------------------------------- cache stats
# process-wide persistent-cache traffic counters, fed by jax.monitoring
# events and read by observability.goodput (the jit_compile_{cold,
# cache_hit} ledger split and the compile_cache_*_total counters)

_CACHE_STATS = {"hits": 0, "misses": 0}
_LISTENER_LOCK = threading.Lock()
_LISTENER_INSTALLED = False
_FLAG_APPLIED_DIR = None


def _on_cache_event(event: str, **kw) -> None:
    if event.endswith("/compilation_cache/cache_hits"):
        _CACHE_STATS["hits"] += 1
    elif event.endswith("/compilation_cache/cache_misses"):
        _CACHE_STATS["misses"] += 1


# jax.monitoring's duration events that the step timeline keeps as
# pt/host/compile (observability/tracer.py), by what each one is
_COMPILE_DURATIONS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


def _on_duration_event(event: str, duration_secs: float, **kw) -> None:
    what = _COMPILE_DURATIONS.get(event)
    if what is None:
        return
    # the event is recorded as it ends, on the thread that compiled
    from .observability import tracer as _tracer
    end = time.perf_counter_ns()
    _tracer.tracer().host_event(
        _tracer.COMPILE_EVENT, end - int(duration_secs * 1e9), end,
        what=what)


def _install_cache_listener() -> None:
    """The process's one listener on jax.monitoring: the cache's hit
    and miss counts, and the compiles' durations for the step timeline
    (kept only while metrics are on)."""
    global _LISTENER_INSTALLED
    with _LISTENER_LOCK:
        if _LISTENER_INSTALLED:
            return
        from jax import monitoring
        monitoring.register_event_listener(_on_cache_event)
        monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        _LISTENER_INSTALLED = True


def compile_cache_stats() -> dict:
    """{'hits': int, 'misses': int} persistent-cache lookups so far."""
    return dict(_CACHE_STATS)


def apply_compile_cache_flag() -> None:
    """Point jax's persistent compilation cache at
    FLAGS_compile_cache_dir if set (enable_compile_cache's rule holds:
    a JAX_COMPILATION_CACHE_DIR from the environment keeps the
    directory and only the threshold is applied). Idempotent and cheap
    — the entry points that trigger compiles (hapi.Model.fit,
    jit.to_static, inference.Predictor/Server) all call it, because
    env-provided flag values never fire on_change hooks. Threshold 0: when an operator
    asks for a persistent cache they mean every executable, including
    the sub-second CPU ones the proof drill measures."""
    global _FLAG_APPLIED_DIR
    from .flags import GLOBAL_FLAGS
    try:
        cache_dir = GLOBAL_FLAGS.get("compile_cache_dir")
    except KeyError:  # registry not fully imported yet
        return
    if not cache_dir or cache_dir == _FLAG_APPLIED_DIR:
        return
    _FLAG_APPLIED_DIR = cache_dir
    enable_compile_cache(cache_dir, min_compile_secs=0.0)
