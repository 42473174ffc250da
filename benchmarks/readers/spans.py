"""Host-clock spans the benchmark's own loop recorded."""

from __future__ import annotations

import statistics
from typing import Any, Dict, Optional


def median_ms(observed: Dict[str, Any], span: str,
              divide_by_counter: Optional[str] = None) -> Optional[float]:
    """Median of the samples of ``span``, optionally divided by a
    counter (a group of ten steps timed together gives ms per step:
    the host's clock is off by half a millisecond, so a 60 ms step is
    not timed alone)."""
    samples = observed.get("spans", {}).get(span) or []
    if not samples:
        return None
    value = statistics.median(samples)
    if divide_by_counter:
        value /= observed["counters"][divide_by_counter]
    return value
