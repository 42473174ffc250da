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

Three more things only the traced program knows are kept here while
metrics are on, all at trace time and nothing per step:

- ``note_kernel``: each Pallas call site notes ``(name, flops,
  bytes)`` — the work one call must do, from its shapes — under the jit
  entry point being traced (``kernel_notes(fn)``), so a kernel's device
  time from a profile can be set against a peak;
- ``note_dropout_mask``: each dropout call site notes the elements its
  hashed keep-mask covers, published per entry point as the gauges
  ``pt_dropout_mask_sites`` / ``pt_dropout_mask_elements``;
- ``note_qkv_grad_summed``: each self-attention site whose three
  projection input gradients are summed before they cross the ``mp``
  link notes itself, published as ``pt_qkv_grad_summed_sites``;
- ``note_ssd_scan_kernel``: each Mamba-2 layer whose scan runs the fused
  kernels notes itself, published as ``pt_ssd_scan_kernel_sites``;
- ``note_bd_attention``: each attention layer under the block-diffusion
  mask whose products run the ``bd_flash_*`` kernels notes itself,
  published as ``pt_bd_attention_sites``;
- ``note_flash_tiles``: each flash attention forward call site notes
  the key tiles one head and sequence of it visits, those of them it
  walks without a mask and those it walks as noisy diagonal sub-tiles
  (``kernels.flash_attention.flash_tile_census``), published summed as
  ``pt_flash_tiles_visited`` / ``pt_flash_tiles_whole`` /
  ``pt_flash_tiles_diagonal``;
- ``note_remat_kept``: each named result that a layer's recomputation
  keeps (``nn.recompute_layer``'s policy) notes its bytes, published as
  ``pt_remat_kept_sites`` / ``pt_remat_kept_bytes``;
- ``op_scopes(fn)``: on demand, the compiled program's
  ``{instruction name: op_name}``. A device profile names an operation
  by its HLO instruction; the ``jax.named_scope`` it was traced under
  (``pt.attn``, ``pt.optimizer``, ... — docs/observability.md) is in
  that instruction's ``op_name`` metadata, which the profile may lack.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from . import metrics as _metrics

__all__ = ["ProgramCardRegistry", "cards", "enabled", "harvest",
           "flops_of", "note_kernel", "note_dropout_mask",
           "note_qkv_grad_summed", "note_ssd_scan_kernel",
           "note_bd_attention", "note_flash_tiles", "note_remat_kept",
           "kernel_notes",
           "op_scopes", "parse_op_names"]

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


# -- kernel work noted at trace time -------------------------------------------

KernelNote = Tuple[str, float, float]      # (name, flops, bytes) of one call

_TLS = threading.local()
_NOTES_LOCK = threading.Lock()
_KERNEL_NOTES: Dict[str, List[KernelNote]] = {}


@contextlib.contextmanager
def tracing(fn_name: str) -> Iterator[None]:
    """Entered by the recompile tracker round the body of a jit entry
    point, which runs only while jax traces it: kernels, dropout sites,
    summed q/k/v gradients and fused scans traced inside note themselves
    under ``fn_name``. The newest trace replaces what the one before
    noted (a retrace, or ``op_scopes`` lowering the entry point again,
    must not count a call site twice)."""
    outer = (getattr(_TLS, "notes", None), getattr(_TLS, "masked", None),
             getattr(_TLS, "summed", None), getattr(_TLS, "scans", None),
             getattr(_TLS, "bd_sites", None), getattr(_TLS, "kept", None),
             getattr(_TLS, "tiles", None))
    _TLS.notes = notes = []
    _TLS.masked = masked = []
    _TLS.summed = summed = []
    _TLS.scans = scans = []
    _TLS.bd_sites = bd_sites = []
    _TLS.kept = kept = []
    _TLS.tiles = tiles = []
    try:
        yield
    finally:
        (_TLS.notes, _TLS.masked, _TLS.summed, _TLS.scans,
         _TLS.bd_sites, _TLS.kept, _TLS.tiles) = outer
        if outer[0] is not None:    # an entry point traced inside another
            outer[0].extend(notes)
            outer[1].extend(masked)
            outer[2].extend(summed)
            outer[3].extend(scans)
            outer[4].extend(bd_sites)
            outer[5].extend(kept)
            outer[6].extend(tiles)
        if _metrics.enabled():
            with _NOTES_LOCK:
                _KERNEL_NOTES[fn_name] = notes
            _metrics.gauge(
                "pt_dropout_mask_sites",
                "dropout call sites whose keep-mask is a counter hash, "
                "in the newest trace of the entry point").set(
                    len(masked), fn=fn_name)
            _metrics.gauge(
                "pt_dropout_mask_elements",
                "elements those sites mask in one call of the entry "
                "point").set(sum(masked), fn=fn_name)
            _metrics.gauge(
                "pt_qkv_grad_summed_sites",
                "self-attention sites whose q/k/v input gradients are "
                "summed before the mp all-reduce, in the newest trace "
                "of the entry point").set(len(summed), fn=fn_name)
            _metrics.gauge(
                "pt_ssd_scan_kernel_sites",
                "Mamba-2 layers whose chunked scan runs the fused Pallas "
                "kernels, in the newest trace of the entry point").set(
                    len(scans), fn=fn_name)
            _metrics.gauge(
                "pt_bd_attention_sites",
                "attention layers under the block-diffusion mask whose "
                "products run the bd_flash kernels, in the newest trace "
                "of the entry point").set(len(bd_sites), fn=fn_name)
            _metrics.gauge(
                "pt_flash_tiles_visited",
                "key tiles that the flash attention forward call sites "
                "visit, a head and sequence each, summed over the sites "
                "of the newest trace of the entry point").set(
                    sum(t[0] for t in tiles), fn=fn_name)
            _metrics.gauge(
                "pt_flash_tiles_whole",
                "those of the visited tiles walked without a mask, "
                "every pair of them allowed and real").set(
                    sum(t[1] for t in tiles), fn=fn_name)
            _metrics.gauge(
                "pt_flash_tiles_diagonal",
                "those of the visited tiles walked as noisy diagonal "
                "sub-tiles under the block-diffusion mask").set(
                    sum(t[2] for t in tiles), fn=fn_name)
            _metrics.gauge(
                "pt_remat_kept_sites",
                "named results that recomputed layers keep across the "
                "backward pass instead of making them again, in the "
                "newest trace of the entry point").set(
                    len(kept), fn=fn_name)
            _metrics.gauge(
                "pt_remat_kept_bytes",
                "bytes of those results in one call of the entry "
                "point").set(sum(kept), fn=fn_name)


def note_kernel(name: str, flops: float, bytes_: float) -> None:
    """Called by a kernel wrapper beside its ``pallas_call``, once per
    traced call site: the FLOPs and HBM bytes one call must do. A no-op
    unless metrics are on and a tracked entry point is being traced."""
    notes = getattr(_TLS, "notes", None)
    if notes is not None and _metrics.enabled():
        notes.append((name, float(flops), float(bytes_)))


@contextlib.contextmanager
def unnoted(when: bool = True) -> Iterator[None]:
    """Kernels traced inside note nothing (``when`` false: they note as
    ever): for a trace whose calls the caller knows the program will not
    run (a ``custom_vjp``'s forward rule traced again by
    ``jax.checkpoint``'s recomputation, where nothing reads its result;
    the function itself traced inside ``layer_primal()``). A noted site
    that never runs would make a kernel's share of the peak
    unreadable."""
    was = getattr(_TLS, "notes", None)
    if when:
        _TLS.notes = None
    try:
        yield
    finally:
        _TLS.notes = was


def note_dropout_mask(elements: int) -> None:
    """Called once per traced dropout call site with the elements it
    masks. A no-op unless metrics are on and a tracked entry point is
    being traced."""
    masked = getattr(_TLS, "masked", None)
    if masked is not None and _metrics.enabled():
        masked.append(int(elements))


def note_qkv_grad_summed() -> None:
    """Called once per traced self-attention site that takes the
    projections whose input gradients are summed before the exchange
    over ``mp``. A no-op unless metrics are on and a tracked entry
    point is being traced."""
    summed = getattr(_TLS, "summed", None)
    if summed is not None and _metrics.enabled():
        summed.append(1)


def note_ssd_scan_kernel() -> None:
    """Called once per traced Mamba-2 layer whose scan the seam
    (``kernels.maybe_ssd_scan``) sends to the fused kernels. A no-op
    unless metrics are on and a tracked entry point is being traced."""
    scans = getattr(_TLS, "scans", None)
    if scans is not None and _metrics.enabled():
        scans.append(1)


def note_bd_attention() -> None:
    """Called once per traced attention layer whose block-diffusion mask
    the seam (``kernels.maybe_flash_attention``) sends to the
    ``bd_flash_*`` kernels. A no-op unless metrics are on and a tracked
    entry point is being traced."""
    sites = getattr(_TLS, "bd_sites", None)
    if sites is not None and _metrics.enabled():
        sites.append(1)


def note_flash_tiles(visited: int, whole: int, diagonal: int) -> None:
    """Called beside a flash attention forward call's ``note_kernel``
    with the census of its tile walk, and silent where that is
    (``unnoted``). A no-op unless metrics are on and a tracked entry
    point is being traced."""
    tiles = getattr(_TLS, "tiles", None)
    if tiles is not None and getattr(_TLS, "notes", None) is not None \
            and _metrics.enabled():
        tiles.append((int(visited), int(whole), int(diagonal)))


def note_remat_kept(bytes_: int) -> None:
    """Called by ``nn.recompute_layer``'s policy once per named result
    it answers is kept, with the result's bytes. A no-op unless metrics
    are on and a tracked entry point is being traced."""
    kept = getattr(_TLS, "kept", None)
    if kept is not None and _metrics.enabled():
        kept.append(int(bytes_))


@contextlib.contextmanager
def layer_primal() -> Iterator[None]:
    """Entered by ``nn.recompute_layer`` round the trace
    ``jax.checkpoint`` makes of a layer. A gradient does not run that
    trace of a ``custom_vjp`` function inside: it runs the function's
    forward rule, traced later and outside this context, and where the
    rule's results are kept, once. A kernel that both traces note
    traces this one under ``unnoted(when=in_layer_primal())``."""
    was, _TLS.layer_primal = in_layer_primal(), True
    try:
        yield
    finally:
        _TLS.layer_primal = was


def in_layer_primal() -> bool:
    return getattr(_TLS, "layer_primal", False)


def kernel_notes(fn_name: str) -> List[KernelNote]:
    """The call sites noted by the newest trace of ``fn_name``."""
    with _NOTES_LOCK:
        return list(_KERNEL_NOTES.get(fn_name, ()))


def reset_kernel_notes() -> None:
    with _NOTES_LOCK:
        _KERNEL_NOTES.clear()


# -- instruction -> op_name, from the compiled text -----------------------------

_INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME_RE = re.compile(r"\bmetadata=\{[^}]*?op_name=\"([^\"]*)\"")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def parse_op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of every instruction in an HLO
    module's text (names are unique in a module).

    What the compiler adds carries no metadata — on a TPU mostly the
    moves between memory spaces (``copy-start``/``copy-done``,
    ``slice-start``/``slice-done`` and the bitcast that joins them),
    several per cent of a train step. Such an instruction takes the
    ``op_name`` of the first instruction that uses its result, through
    other such instructions: the move is charged to the work that
    needed the data. ``""`` where no user has one either.

    What the compiler rewrites into a kernel of its own carries a name
    it made up (XLA:TPU turns ``lax.ragged_dot`` into Mosaic calls
    named ``ragged-dot-none``): an ``op_name`` without a ``/`` is no
    trace of the program's. Such an instruction is charged like a move,
    and where nothing in its computation uses its result (a gradient
    that leaves a loop's or a branch's body) to the work that made its
    newest operand."""
    own: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    renamed = set()
    for line in hlo_text.splitlines():
        m = _INSTRUCTION_RE.match(line)
        if not m:
            continue
        name = m.group(1)
        found = _OP_NAME_RE.search(line)
        own[name] = found.group(1) if found else ""
        if found and "/" not in own[name]:
            own[name] = ""
            renamed.add(name)
        operands[name] = _OPERAND_RE.findall(line[m.end():])
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)

    def inherited(name: str, through: Dict[str, List[str]],
                  depth: int = 8) -> str:
        for other in through.get(name, ()) if depth else ():
            op_name = own.get(other) or inherited(other, through,
                                                  depth - 1)
            if op_name:
                return op_name
        return ""

    newest_first = {name: ops[::-1] for name, ops in operands.items()}
    return {name: op_name or inherited(name, users)
            or (inherited(name, newest_first) if name in renamed else "")
            for name, op_name in own.items()}


def op_scopes(fn_name: str) -> Optional[Dict[str, str]]:
    """Lower and compile the jit entry point ``fn_name`` again from the
    abstract signature its newest trace left with the recompile tracker
    (kept only while metrics are on), and return the compiled program's
    ``{instruction name: op_name}``. Costs a compile, or a load from
    the persistent cache: for whoever asks (a profile's reader, an
    operator), never on a step. The trace it causes is not counted as
    a recompilation. ``None`` when there is nothing to lower from, or
    metrics are off (ask while they are on, as they were at the
    trace: the program lowered must be the one that ran)."""
    from . import recompile as _recompile
    rec = _recompile.tracker().get(fn_name)
    if rec is None:
        return None
    compiled = rec.compile_again()
    if compiled is None:
        return None
    return parse_op_names(compiled.as_text())
