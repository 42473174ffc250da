"""Per-layer numbers read through the names the program gives its own
work (docs/observability.md): ``pt.<block>`` device scopes, the
``name=`` of each Pallas call with the work it noted at trace time, and
the ``pt/train_step/*`` host spans.

A device operation in the trace is named by its HLO instruction; its
scope is in that instruction's ``op_name``, which the chip's profile
does not carry (its events hold times only). The program's own
``observability.xprof.op_scopes`` lowers the entry point again (a load
from the persistent cache) and gives ``{instruction: op_name}``. Which
entry point to ask for is the metric file's ``fn_pattern``, matched
against the recompile tracker's names.

The reduction in ``benchmarks/trace.py`` keeps only ``bench/``
annotations, so the profile is opened once more here for the ``pt/``
ones (its file is still there when the readers run); everything is
built once and shared by this module's readers through ``observed``.

A program without these names (an older checkout) gives every reader
here nothing to read: ``None``, never an error."""

from __future__ import annotations

import re
import time
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .. import trace as tr
from ..harness import log

_VIEW_KEY = "_program_view"
SCOPE_RE = re.compile(r"pt\.[A-Za-z_]+")
UNSCOPED = "unscoped"
ANNOTATION_PREFIXES = ("pt/", "bench/")
# below this share of the slice's device time joined to an op_name the
# scope map is not the program that ran: report nothing
MIN_JOINED_SHARE = 0.9


# -- pure parts (checked on a hand-built trace) -------------------------------

def instruction(event_name: str) -> str:
    """The HLO instruction name of an "XLA Ops" event: the chip names
    the event by the whole HLO line (``%fusion.3 = bf16[..] fusion(``),
    the CPU backend by the bare name."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def block_of(op_name: str) -> Tuple[Optional[str], bool]:
    """The block an operation is charged to: the last ``pt.<name>``
    token of its ``op_name``. Merged metadata (``a;b``) may name two
    blocks: the first counts, and the second value says so."""
    blocks = []
    for part in op_name.split(";"):
        found = SCOPE_RE.findall(part)
        if found:
            blocks.append(found[-1])
    if not blocks:
        return None, False
    return blocks[0], len(set(blocks)) > 1


def self_times(events: Iterable[tr.Event]) -> List[Tuple[tr.Event, float]]:
    """Each event with the time in which it is the innermost event
    running (its duration less what events started inside it cover):
    the self times sum to the union of the intervals exactly, whether
    or not the lane nests (a ``while`` round its body) or overlaps."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    charge = [0.0] * len(evs)
    points = []
    for i, e in enumerate(evs):
        points.append((e.start, 1, i))
        points.append((e.start + e.dur, 0, i))
    points.sort()                   # at one instant, ends before starts
    active: List[int] = []
    cursor = 0.0
    for t, is_start, i in points:
        if active:
            charge[active[-1]] += t - cursor
        cursor = t
        if is_start:
            active.append(i)
        else:
            active.remove(i)
    return list(zip(evs, charge))


def charge_blocks(events: Iterable[tr.Event], scopes: Dict[str, str]
                  ) -> Dict[str, Any]:
    """Device time by block: every operation's self time goes to the
    block of its instruction's ``op_name`` (a fusion whole to the
    fusion instruction's), to ``unscoped`` where it names none.
    ``by_kind`` holds the same time by (kind of operation, block)."""
    by_block: Dict[str, float] = defaultdict(float)
    by_kind: Dict[Tuple[str, str], List[float]] = defaultdict(
        lambda: [0.0, 0])
    joined = merged = total = 0.0
    for e, t in self_times(events):
        total += t
        op_name = scopes.get(instruction(e.name))
        if op_name is not None:
            joined += t
        block, two = block_of(op_name or "")
        if two:
            merged += t
        by_block[block or UNSCOPED] += t
        kind = by_kind[(tr.op_kind(e.name), block or UNSCOPED)]
        kind[0] += t
        kind[1] += 1
    return {"by_block": dict(by_block), "by_kind": dict(by_kind),
            "total_ns": total, "joined_ns": joined, "merged_ns": merged}


def kernel_events(events: Iterable[tr.Event], kernels: Iterable[str]
                  ) -> Tuple[float, int]:
    """Summed duration and count of the Mosaic calls named (``name=``)
    one of ``kernels``: XLA names the custom call's instruction after
    the innermost scope, which is the kernel's name."""
    rx = re.compile(r"^(?:%s)(?:\.\d+)?$"
                    % "|".join(re.escape(k) for k in kernels))
    total, n = 0.0, 0
    for e in events:
        if rx.match(instruction(e.name)):
            total += e.dur
            n += 1
    return total, n


# -- the profile, once more ---------------------------------------------------

def read_annotations(path: str) -> List[tr.Event]:
    """The program's and the benchmark's annotations in the profile."""
    from jax.profiler import ProfileData
    annotations: List[tr.Event] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION_PREFIXES):
                    annotations.append(tr.Event(
                        e.name, float(e.start_ns), float(e.duration_ns)))
    annotations.sort(key=lambda a: a.start)
    return annotations


def entry_point(fn_pattern: str) -> Optional[str]:
    """The traced jit entry point whose name matches ``fn_pattern``
    (the newest, should several), from the recompile tracker."""
    from paddle_tpu import observability as obs
    rx = re.compile(fn_pattern)
    names = [n for n, s in obs.recompile_tracker().snapshot().items()
             if rx.search(n) and s["traces"]]
    return names[-1] if names else None


def compiled_scopes(fn_name: str) -> Optional[Dict[str, str]]:
    """``xprof.op_scopes(fn_name)``, asked with metrics on as they were
    when the step was traced. ``None`` where the program has no such
    function (a checkout from before it) or nothing was kept."""
    import paddle_tpu as pt
    from paddle_tpu.observability import xprof
    op_scopes = getattr(xprof, "op_scopes", None)
    if op_scopes is None:
        return None
    was = pt.get_flags(["enable_metrics"])["enable_metrics"]
    pt.set_flags({"enable_metrics": True})
    try:
        return op_scopes(fn_name)
    finally:
        pt.set_flags({"enable_metrics": was})


def view(observed: Dict[str, Any], fn_pattern: str
         ) -> Optional[Dict[str, Any]]:
    """Everything this module's readers share, built at the first call
    and kept in ``observed``: device 0's operations of the slice, the
    annotations, the block charge and the entry point's name. Logs the
    named breakdown and the idle gaps by ``pt/`` and ``bench/`` span."""
    if _VIEW_KEY in observed:
        return observed[_VIEW_KEY]
    observed[_VIEW_KEY] = None
    data = observed.get("trace")
    window = tr.slice_window(data) if data is not None else None
    if window is None or not data.ops:
        return None
    events = tr.clip(data.ops[sorted(data.ops)[0]], *window)  # device 0
    steps = observed.get("counters", {}).get("trace_steps") or 1
    fn = entry_point(fn_pattern)
    t0 = time.perf_counter()
    try:
        annotations = read_annotations(data.path)
        scopes = (compiled_scopes(fn) if fn else None) or {}
    except Exception as e:  # noqa: BLE001 — a reader reports, never raises
        log(f"program view: {type(e).__name__}: {e}")
        annotations, scopes = [], {}
    log(f"program view: annotations and xprof.op_scopes({fn!r}) read in "
        f"{time.perf_counter() - t0:.1f}s: {len(scopes)} instructions")
    charge = charge_blocks(events, scopes)
    named = any(b != UNSCOPED for b in charge["by_block"])
    joined = charge["joined_ns"] / charge["total_ns"] \
        if charge["total_ns"] else 0.0
    if not named or joined < MIN_JOINED_SHARE:
        log(f"program view: no block reading: {joined:.1%} of the device "
            f"time joined to an instruction of the scope map, pt. "
            f"scopes {'found' if named else 'not found'} (a program "
            "without them, or an executable that a checkout without "
            "them left in the persistent cache: its key leaves op_name "
            "metadata out)")
        charge = None
    else:
        def per_step(ns: float) -> str:
            return f"{ns / 1e6 / steps:.3f}"
        log("program view: ms per step by block: " + " ".join(
            f"{b}={per_step(t)}" for b, t in sorted(
                charge["by_block"].items(), key=lambda kv: -kv[1]))
            + f"; sum {per_step(charge['total_ns'])} against busy "
            f"{per_step(tr.busy_union(events))}; joined {joined:.2%}, "
            "merged metadata naming two blocks "
            f"{charge['merged_ns'] / charge['total_ns']:.2%}")
        kinds = sorted(charge["by_kind"].items(), key=lambda kv: -kv[1][0])
        for what, rows in (
                ("largest kinds", kinds[:12]),
                ("largest unscoped kinds",
                 [kv for kv in kinds if kv[0][1] == UNSCOPED][:6])):
            log(f"program view: {what}, ms per step [block]: " + "; ".join(
                f"{kind} x{n} {per_step(t)} [{block}]"
                for (kind, block), (t, n) in rows))
    in_slice = [a for a in tr.clip(annotations, *window)
                if a.name != tr.ANNOTATION_PREFIX + "slice"]
    gaps = tr.attribute_gaps(tr.idle_gaps(events, *window), in_slice)
    log("program view: idle seconds by innermost pt/ or bench/ span: "
        + " ".join(f"{n}={s:.6f}" for n, s in gaps))
    observed[_VIEW_KEY] = {"events": events, "annotations": in_slice,
                           "charge": charge, "fn": fn}
    return observed[_VIEW_KEY]


# -- the readers the metric files name ----------------------------------------

def _steps(observed: Dict[str, Any], per_counter: str) -> Optional[int]:
    return observed.get("counters", {}).get(per_counter) or None


def block_ms_per_unit(observed: Dict[str, Any], scope: str,
                      fn_pattern: str, per_counter: str
                      ) -> Optional[float]:
    """Device time of the operations charged to ``scope`` (``pt.attn``,
    ..., or ``unscoped``), forward and backward together, per step.
    0.0 for a block the program names but this slice never ran;
    ``None`` where the program names no block at all."""
    v = view(observed, fn_pattern)
    steps = _steps(observed, per_counter)
    if v is None or v["charge"] is None or not steps:
        return None
    return v["charge"]["by_block"].get(scope, 0.0) / 1e6 / steps


def kernel_ms_per_unit(observed: Dict[str, Any], kernels: List[str],
                       fn_pattern: str, per_counter: str
                       ) -> Optional[float]:
    """Summed device time of the Mosaic calls named one of ``kernels``
    per step; ``None`` when the slice ran none."""
    v = view(observed, fn_pattern)
    steps = _steps(observed, per_counter)
    if v is None or not steps:
        return None
    total, n = kernel_events(v["events"], kernels)
    return total / 1e6 / steps if n else None


def kernel_peak_pct(observed: Dict[str, Any], kernels: List[str],
                    fn_pattern: str, per_counter: str,
                    peak: str = "bf16_flops_per_s") -> Optional[float]:
    """100 x the FLOPs the kernels' call sites noted at trace time x
    steps / their device time / the chip's ``peak``. ``None`` unless
    the slice ran exactly one event a step for every noted site: a
    site traced but not run, or run twice, would make the share
    wrong."""
    v = view(observed, fn_pattern)
    steps = _steps(observed, per_counter)
    if v is None or v["fn"] is None or not steps:
        return None
    from paddle_tpu.observability import xprof
    notes = getattr(xprof, "kernel_notes", lambda fn: [])(v["fn"])
    sites = [n for n in notes if n[0] in kernels]
    total, events = kernel_events(v["events"], kernels)
    if not sites or not total or events != len(sites) * steps:
        log(f"kernel_peak_pct {kernels}: {events} events over {steps} "
            f"steps against {len(sites)} noted call sites: no reading")
        return None
    import jax

    from .. import arithmetic
    try:
        peaks = arithmetic.peaks_for(jax.devices()[0].device_kind)
    except KeyError as e:
        log(f"kernel_peak_pct {kernels}: {e}")
        return None
    flops = sum(n[1] for n in sites) * steps
    return 100.0 * flops / (total / 1e9) / peaks[peak]


def span_ms_per_unit(observed: Dict[str, Any], prefix: str,
                     fn_pattern: str, per_counter: str
                     ) -> Optional[float]:
    """Summed duration of the program's host spans whose name starts
    with ``prefix``, inside the slice, per step."""
    v = view(observed, fn_pattern)
    steps = _steps(observed, per_counter)
    if v is None or not steps:
        return None
    durs = [a.dur for a in v["annotations"] if a.name.startswith(prefix)]
    return sum(durs) / 1e6 / steps if durs else None
