"""Compiled-program analytics: per-function XLA cost/memory cards.

The reference exposes per-op cost through its profiler events; on TPU
the unit of execution is the whole XLA program, and XLA itself already
carries the numbers that matter — the compiler's cost model
(``compiled.cost_analysis()``: FLOPs, bytes accessed) and the buffer
assignment (``compiled.memory_analysis()``: peak/temp/argument bytes).
This module harvests them at trace time into a **program card** per jit
entry point, keyed like the recompile tracker (one card per traced
input signature), so a live process can answer "what does my compiled
step cost" without a profiler run — the XLA-level cost visibility the
Julia-to-TPU paper assumes, on a serving-friendly pull path.

Cards feed three consumers:

- ``/varz`` on the observability HTTP server (full card JSON),
- the ``program_flops`` / ``program_peak_bytes`` gauges on ``/metrics``
  plus the achieved-FLOPs gauge ``hapi.fit`` derives per step,
- ``metrics.json`` (``export_all``) → ``tools/trace_report.py``.

Harvesting re-runs ``lower().compile()`` once per traced signature (the
AOT path does not share the dispatch cache), so it is gated on BOTH
``FLAGS_enable_metrics`` and ``FLAGS_program_analytics``: a trace-time
cost only, never a steady-state one. Backends whose analyses are empty
or unsupported produce a card with an explicit ``unavailable`` marker
instead of an error (the CPU fallback contract tested in
tests/test_observability.py).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import metrics as _metrics

__all__ = ["ProgramCardRegistry", "cards", "enabled", "harvest",
           "flops_of"]

# Cost-analysis keys promoted onto the card top level when present.
_COST_KEYS = ("flops", "transcendentals", "bytes accessed")
# CompiledMemoryStats attributes promoted (jax >= 0.4 names).
_MEM_ATTRS = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")


def enabled() -> bool:
    """Program analytics run only when metrics are on AND the dedicated
    flag is on (both default-off overall: metrics gate the subsystem)."""
    if not _metrics.enabled():
        return False
    try:
        from ..flags import GLOBAL_FLAGS
        return bool(GLOBAL_FLAGS.get("program_analytics"))
    except Exception:
        return False


def _cost_dict(compiled) -> Dict[str, float]:
    """cost_analysis() as {name: float}; a backend without the analysis
    returns None."""
    cost = compiled.cost_analysis() or {}
    return {str(k): float(v) for k, v in cost.items()
            if isinstance(v, (int, float))}


def _memory_dict(compiled) -> Dict[str, int]:
    mem = compiled.memory_analysis()
    if mem is None:
        return {}
    if isinstance(mem, dict):
        return {str(k): int(v) for k, v in mem.items()
                if isinstance(v, (int, float))}
    out = {}
    for attr in _MEM_ATTRS:
        v = getattr(mem, attr, None)
        if isinstance(v, (int, float)):
            out[attr] = int(v)
    return out


class ProgramCardRegistry:
    """name -> {signature -> card} store (mirrors RecompileTracker
    keying so cards and recompile records line up in /varz)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cards: Dict[str, Dict[str, Dict[str, Any]]] = {}

    def put(self, name: str, signature: str,
            card: Dict[str, Any]) -> None:
        with self._lock:
            self._cards.setdefault(name, {})[signature] = card

    def get(self, name: str) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return dict(self._cards.get(name, {}))

    def latest(self, name: str) -> Optional[Dict[str, Any]]:
        """Most recently harvested card for a function (insertion
        order), or None."""
        with self._lock:
            by_sig = self._cards.get(name)
            if not by_sig:
                return None
            return list(by_sig.values())[-1]

    def snapshot(self) -> Dict[str, Dict[str, Dict[str, Any]]]:
        with self._lock:
            return {n: dict(sigs) for n, sigs in self._cards.items()}

    def reset(self) -> None:
        with self._lock:
            self._cards.clear()


_CARDS = ProgramCardRegistry()


def cards() -> ProgramCardRegistry:
    return _CARDS


def harvest(name: str, lowerable: Callable, avals_args: tuple,
            avals_kwargs: dict, signature: str) -> Optional[Dict[str, Any]]:
    """Lower+compile ``lowerable`` for the given abstract signature and
    record a program card. Never raises: every failure mode becomes an
    ``unavailable`` marker on the card (or a skipped harvest when even
    lowering is impossible)."""
    t0 = time.perf_counter()
    card: Dict[str, Any] = {"fn": name, "signature": signature,
                            "harvested_unix": time.time()}
    try:
        compiled = lowerable.lower(*avals_args, **avals_kwargs).compile()
    except Exception as e:  # noqa: BLE001 — analytics must never break a step
        card["unavailable"] = f"lower/compile failed: {type(e).__name__}: {e}"
        _CARDS.put(name, signature, card)
        return card
    try:
        cost = _cost_dict(compiled)
    except Exception as e:  # noqa: BLE001
        cost, card["cost_error"] = {}, f"{type(e).__name__}: {e}"
    try:
        mem = _memory_dict(compiled)
    except Exception as e:  # noqa: BLE001
        mem, card["memory_error"] = {}, f"{type(e).__name__}: {e}"
    card["cost_analysis"] = cost
    card["memory_analysis"] = mem
    if not cost and not mem:
        card["unavailable"] = "backend returned empty analyses"
    for k in _COST_KEYS:
        if k in cost:
            card[k.replace(" ", "_")] = cost[k]
    peak = sum(mem.get(a, 0) for a in ("argument_size_in_bytes",
                                       "output_size_in_bytes",
                                       "temp_size_in_bytes"))
    if mem:
        card["peak_bytes_estimate"] = int(peak)
    card["harvest_seconds"] = time.perf_counter() - t0
    _CARDS.put(name, signature, card)

    # gauges so the card headline numbers ride the Prometheus page
    if "flops" in cost:
        _metrics.gauge(
            "program_flops",
            "XLA cost-model FLOPs of the latest compiled program"
        ).set(cost["flops"], fn=name)
    if mem:
        _metrics.gauge(
            "program_peak_bytes",
            "argument+output+temp bytes of the latest compiled program"
        ).set(float(peak), fn=name)
    _metrics.histogram(
        "program_harvest_seconds",
        "wall time of program-card harvests (trace-time only)",
        buckets=(0.01, 0.05, 0.1, 0.5, 1, 5, 30, 120)
    ).observe(card["harvest_seconds"], fn=name)
    return card


def flops_of(name: str) -> Optional[float]:
    """Cost-model FLOPs of the latest card for ``name`` (None when no
    card or the backend had no cost model) — feeds the achieved-FLOPs
    gauge in hapi.fit."""
    card = _CARDS.latest(name)
    if not card:
        return None
    v = card.get("flops")
    return float(v) if v else None
