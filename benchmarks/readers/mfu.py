"""The whole step's share of the chips' peak, over the traced slice."""

from __future__ import annotations

from typing import Any, Dict, Optional

from .. import arithmetic
from ..harness import log


def mfu_pct(observed: Dict[str, Any], flops_counter: str,
            peak: str = "bf16_flops_per_s") -> Optional[float]:
    """100 x the model FLOPs of the traced slice (the runner's count
    from shapes and from the pairs the step itself counted; no
    recomputation) / the slice's time on the profile's clock / (chips x
    the chip's ``peak``). The slice is one group of steps and its loss
    fetch, so host gaps count against the share, as they do against
    ``train_tokens_per_s``. ``None`` where the runner counted no FLOPs
    or the device's peaks are not published."""
    counters = observed.get("counters", {})
    flops, chips = counters.get(flops_counter), counters.get("chips")
    window_s = (observed.get("trace_summary") or {}).get("window_s")
    if not flops or not chips or not window_s:
        return None
    import jax
    try:
        peaks = arithmetic.peaks_for(jax.devices()[0].device_kind)
    except KeyError as e:
        log(f"mfu_pct: {e}")
        return None
    return 100.0 * flops / window_s / (chips * peaks[peak])
