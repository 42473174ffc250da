"""Device-side numbers from the traced slice."""

from __future__ import annotations

from typing import Any, Dict, Optional

from .. import trace as tr


def _slice(observed: Dict[str, Any], lane: str):
    data = observed.get("trace")
    if data is None:
        return None
    window = tr.slice_window(data)
    lanes = getattr(data, lane)
    if window is None or not lanes:
        return None
    first = sorted(lanes)[0]          # device 0
    return tr.clip(lanes[first], *window)


def ms_per_unit(observed: Dict[str, Any], pattern: str, per_counter: str,
                lane: str = "ops") -> Optional[float]:
    """Summed device time, on device 0, of the events of ``lane``
    ("ops" or "modules") whose name matches ``pattern``, per unit of
    ``per_counter`` (steps in the slice). ``None`` when no event
    matches: the kernel is not in this program."""
    events = _slice(observed, lane)
    units = observed.get("counters", {}).get(per_counter)
    if events is None or not units:
        return None
    total, n = tr.sum_matching(events, pattern)
    if n == 0:
        return None
    return total / 1e6 / units


def idle_pct(observed: Dict[str, Any]) -> Optional[float]:
    """100 x (1 - union of device-op intervals / traced slice),
    averaged over the chips."""
    s = observed.get("trace_summary") or {}
    if not s.get("window_s"):
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
