"""A kernel's share of the chip's peak where a step runs a noted call
site more than once: a layer recomputed in the backward pass
(``jax.checkpoint``) is traced once, so its forward kernel is noted
once, and runs twice a step. ``readers.program.kernel_peak_pct`` reads
nothing there (it wants one event a step for every noted site)."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from ..harness import log
from . import program


def kernel_peak_pct_per_event(observed: Dict[str, Any], kernels: List[str],
                              fn_pattern: str, per_counter: str,
                              peak: str = "bf16_flops_per_s"
                              ) -> Optional[float]:
    """100 x the FLOPs the kernels' events did / their device time / the
    chip's ``peak``. Every event of a call site does the FLOPs the site
    noted at trace time, so the FLOPs are the sites' sum x steps x the
    events a site ran a step. ``None`` unless that is a whole number of
    at least one, the same for the slice as a whole: a site traced but
    not run, or run in some steps only, would make the share wrong."""
    v = program.view(observed, fn_pattern)
    steps = program._steps(observed, per_counter)
    if v is None or v["fn"] is None or not steps:
        return None
    from paddle_tpu.observability import xprof
    notes = getattr(xprof, "kernel_notes", lambda fn: [])(v["fn"])
    sites = [n for n in notes if n[0] in kernels]
    total, events = program.kernel_events(v["events"], kernels)
    runs = len(sites) * steps
    if not sites or not total or not events or events % runs:
        log(f"kernel_peak_pct_per_event {kernels}: {events} events over "
            f"{steps} steps against {len(sites)} noted call sites: no "
            "reading")
        return None
    import jax

    from .. import arithmetic
    try:
        peaks = arithmetic.peaks_for(jax.devices()[0].device_kind)
    except KeyError as e:
        log(f"kernel_peak_pct_per_event {kernels}: {e}")
        return None
    flops = sum(n[1] for n in sites) * steps * (events // runs)
    return 100.0 * flops / (total / 1e9) / peaks[peak]
