"""Numbers the program counted itself and the runner fetched with the
loss (``observed["counters"]``)."""

from __future__ import annotations

from typing import Any, Dict, Optional


def value(observed: Dict[str, Any], counter: str) -> Optional[float]:
    """The counter as the runner reduced it over the window's fetches;
    ``None`` where this program counts no such thing."""
    return observed.get("counters", {}).get(counter)
