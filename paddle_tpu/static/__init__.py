"""Static-graph programming surface: Program, Executor, Scope, TrainStep.

TPU-native redesign of the reference's static core
(/root/reference/paddle/fluid/framework/: program_desc.h, scope.h:46,
executor.h:53; python/paddle/fluid/framework.py Program :3901,
executor.py Executor.run :900). The mapping:

- ProgramDesc (protobuf op list) → **traced jaxpr**: a Program wraps a pure
  Python function; tracing it IS program construction, XLA compilation IS
  the pass pipeline, and the compiled executable replaces the op-by-op
  C++ interpreter loop (executor.cc:465-472).
- Scope (hierarchical name→Variable map) → :class:`Scope`, a name→array
  store with parent-chain lookup; it holds params/optimizer/buffer state
  between steps and is threaded through compiled programs functionally
  (donated, so XLA updates in place — no copy per step).
- Executor.run(feed/fetch) keeps its exact shape: feeds are arrays bound to
  placeholder names, fetches name outputs.
- append_backward + optimizer ops → :class:`TrainStep`, which fuses
  forward, jax.grad backward, and the optimizer update into ONE compiled
  XLA program (the reference needs three pass systems for this).
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..core import as_label_tuple
import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as _random
from ..errors import NotFoundError
from ..flags import GLOBAL_FLAGS
from ..nn.layer import Layer, functional_call
from ..optimizer import Optimizer
from .. import observability as _obs


class Scope:
    """Hierarchical variable store (ref: scope.h:46)."""

    def __init__(self, parent: Optional["Scope"] = None) -> None:
        self._vars: Dict[str, Any] = {}
        self._parent = parent
        self._kids: List[Scope] = []

    def var(self, name: str, value=None):
        if name not in self._vars:
            self._vars[name] = value
        return self._vars[name]

    def find_var(self, name: str):
        scope: Optional[Scope] = self
        while scope is not None:
            if name in scope._vars:
                return scope._vars[name]
            scope = scope._parent
        raise NotFoundError(f"variable '{name}' not found in scope chain")

    def has_var(self, name: str) -> bool:
        try:
            self.find_var(name)
            return True
        except NotFoundError:
            return False

    def set_var(self, name: str, value) -> None:
        self._vars[name] = value

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self._kids.append(kid)
        return kid

    def drop_kids(self) -> None:
        self._kids.clear()

    def local_var_names(self) -> List[str]:
        return list(self._vars)

    def as_dict(self) -> Dict[str, Any]:
        return dict(self._vars)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


class Program:
    """A compiled-function program.

    ``fn(state: dict, feeds: dict) -> (new_state: dict, fetches: dict)``
    where ``state`` holds named persistent variables (params, optimizer
    slots, stats). Feeds/fetches are name-keyed, matching Executor.run's
    reference API (executor.py:900). State buffers are donated.
    """

    def __init__(self, fn: Callable, state_names: Optional[Sequence[str]]
                 = None, name: str = "program") -> None:
        self.fn = fn
        self.name = name
        self.state_names = list(state_names) if state_names else None
        self._compiled = None

    def _get_compiled(self):
        if self._compiled is None:
            self._compiled = jax.jit(self.fn, donate_argnums=(0,))
        return self._compiled

    def run(self, state: Dict[str, Any], feeds: Dict[str, Any]):
        return self._get_compiled()(state, feeds)

    def clone(self, for_test: bool = False) -> "Program":
        """(ref: framework.py Program.clone: for_test=True prunes
        training-only ops — dropout becomes identity, BN uses running
        stats). Here the model call is re-run with the eval-mode flag:
        the fn is wrapped so any Layer honoring training-mode sees
        eval during trace."""
        if not for_test:
            return Program(self.fn, self.state_names, self.name + "_clone")

        fn = self.fn

        def eval_fn(state, feeds):
            from ..nn.layer import eval_mode
            with eval_mode():
                return fn(state, feeds)

        return Program(eval_fn, self.state_names, self.name + "_test")


class Executor:
    """(ref: executor.py:900 / executor.cc:180). Holds the scope, binds
    feeds, runs compiled programs, returns fetches as numpy."""

    def __init__(self, place=None) -> None:
        from ..core.place import get_device
        self.place = place if place is not None else get_device()

    @property
    def scope(self) -> Scope:
        # resolved at ACCESS time, not construction: fluid.scope_guard
        # must cover Executors built before the guard (the reference
        # executor reads the global scope per run, executor.py:1089)
        return global_scope()

    def run(self, program: Program, feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[Sequence[str]] = None,
            scope: Optional[Scope] = None, return_numpy: bool = True):
        scope = scope or self.scope
        feed = feed or {}
        feed = {k: jnp.asarray(v) for k, v in feed.items()}
        state_names = program.state_names
        if state_names is None:
            state = scope.as_dict()
        else:
            state = {n: scope.find_var(n) for n in state_names}
        new_state, fetches = program.run(state, feed)
        for k, v in new_state.items():
            scope.set_var(k, v)
        if GLOBAL_FLAGS.get("check_nan_inf"):
            _check_nan_inf(fetches, program.name)
        if fetch_list is None:
            out = fetches
        else:
            out = [fetches[name] for name in fetch_list]
        if return_numpy:
            out = jax.tree.map(np.asarray, out)
        return out

    def train_from_dataset(self, program, dataset,
                           input_slots: Optional[Sequence[str]] = None,
                           label_slots: Optional[Sequence[str]] = None,
                           epochs: int = 1, drop_last: bool = True,
                           print_period: int = 0,
                           fetch_handler: Optional[Callable] = None):
        """Drive a TrainStep from a file-backed Dataset
        (ref: executor.py:1572 train_from_dataset → C++ Trainer loop
        hogwild_worker.cc:191 TrainFiles; here the C++ data feed threads
        produce batches and the hot loop is one donated-buffer XLA call).

        - program: a TrainStep (or ShardedTrainStep) — the fused
          train program.
        - dataset: data.QueueDataset / data.InMemoryDataset with slots
          declared; `input_slots`/`label_slots` name which slots feed the
          model args vs the loss labels (default: all-but-last / last).
        - drop_last: skip the final partial batch (avoids recompiling the
          program for a second batch shape).
        Returns per-epoch mean loss list.
        """
        names = dataset.slot_names()
        if input_slots is None or label_slots is None:
            input_slots = names[:-1]
            label_slots = names[-1:]
        history: List[float] = []
        step_idx = 0
        for _ in range(int(epochs)):
            # HOT LOOP: no host sync per step — the loss stays a device
            # array in a running sum fetched once per epoch (the reference
            # keeps Python out of the loop entirely: hogwild_worker.cc:191;
            # forcing float(loss) each step would block async dispatch).
            total = None
            count = 0
            for batch in dataset:
                rows = batch[names[0]].shape[0]
                if drop_last and rows < dataset._batch_size:
                    continue
                args = tuple(batch[n] for n in input_slots)
                labels = tuple(batch[n] for n in label_slots)
                metrics = program(*args, labels=labels)
                total = metrics["loss"] if total is None \
                    else total + metrics["loss"]
                count += 1
                step_idx += 1
                if print_period and step_idx % print_period == 0:
                    print(f"step {step_idx}: "
                          f"loss={float(metrics['loss']):.6f}")
                if fetch_handler is not None:
                    fetch_handler(metrics)
            history.append(float(total) / count if count else 0.0)
        return history

    def infer_from_dataset(self, program, dataset,
                           input_slots: Optional[Sequence[str]] = None,
                           drop_last: bool = False,
                           dump_fields: Optional[Sequence[str]] = None,
                           dump_fields_path: Optional[str] = None):
        """Inference counterpart (ref: executor.py:1451): run a callable
        program over every batch, return list of outputs.

        ``dump_fields``/``dump_fields_path`` mirror the reference
        DeviceWorker dump (device_worker.cc DumpField: per-instance
        tab-separated slot values + prediction written to a file, the
        PS-job audit trail). Fields name input slots to echo; the
        program output is always dumped as the last column.
        """
        names = dataset.slot_names()
        if input_slots is None:
            input_slots = names
        if dump_fields and dump_fields_path is None:
            raise ValueError(
                "dump_fields given without dump_fields_path — the "
                "audit dump would be silently dropped")
        dump_f = None
        if dump_fields_path is not None:
            import os
            os.makedirs(os.path.dirname(dump_fields_path) or ".",
                        exist_ok=True)
            dump_f = open(dump_fields_path, "w")
            dump_fields = list(dump_fields or [])
        outs = []
        try:
            for batch in dataset:
                rows = batch[names[0]].shape[0]
                if drop_last and rows < dataset._batch_size:
                    continue
                args = tuple(batch[n] for n in input_slots)
                out = program(*args)
                outs.append(out)
                if dump_f is not None:
                    self._dump_batch(dump_f, batch, dump_fields, out,
                                     rows)
        finally:
            if dump_f is not None:
                dump_f.close()
        return outs

    @staticmethod
    def _dump_batch(f, batch, fields: Sequence[str], out,
                    rows: int) -> None:
        """One line per instance: field:value... \t pred:... (the
        reference's DumpField format, device_worker.cc). The row count
        comes from the BATCH (outputs may carry scalar aux leaves);
        every output leaf with a matching leading dim contributes a
        pred column."""
        host_fields = {name: np.asarray(batch[name]) for name in fields}
        pred_leaves = [np.asarray(leaf) for leaf in jax.tree.leaves(out)]
        pred_leaves = [a for a in pred_leaves
                       if a.ndim >= 1 and a.shape[0] == rows]
        for i in range(rows):
            cols = []
            for name in fields:
                v = host_fields[name][i].ravel()
                cols.append(name + ":" + ",".join(str(x) for x in v))
            for a in pred_leaves:
                cols.append("pred:" + ",".join(
                    f"{float(x):.6g}" for x in a[i].ravel()))
            f.write("\t".join(cols) + "\n")


def _check_nan_inf(tree, what: str) -> None:
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating) and \
                not np.isfinite(arr).all():
            raise FloatingPointError(
                f"NaN/Inf detected in {what} output {path}"
                " (FLAGS_check_nan_inf)")


# ---------------------------------------------------------------------------
# TrainStep — the fused train program builder
# ---------------------------------------------------------------------------



def _note_nonfinite_host(fired: bool) -> None:
    if not fired:
        return
    try:
        from ..observability import flight as _flight
        _obs.counter(
            "nonfinite_steps_total",
            "train steps whose gradients contained NaN/Inf — the "
            "optimizer/scaler/buffer update was skipped in-graph "
            "(skip-step guard, FLAGS_skip_nonfinite_steps)").inc()
        _flight.record("nonfinite_step", force=True)
    # ptlint: disable=silent-failure -- runs inside a jax.debug.callback: telemetry must never break the dispatch stream
    except Exception:  # telemetry must never break the stream
        pass


@jax.named_scope("pt.probe")
def probe_nonfinite(found_inf) -> None:
    """Stream the skip-step guard's verdict to the host (traced
    context): async jax.debug.callback like anomaly.probe — baked in
    at trace time only while metrics are on, never a host sync."""
    if not _obs.enabled():
        return
    # register at trace time so the TYPE line is on /metrics before
    # the first incident
    # ptlint: disable=trace-purity -- deliberate trace-time registration: creating the counter early puts its TYPE line on /metrics before the first incident; the inc() itself rides the deferred callback
    _obs.counter(
        "nonfinite_steps_total",
        "train steps whose gradients contained NaN/Inf — the "
        "optimizer/scaler/buffer update was skipped in-graph "
        "(skip-step guard, FLAGS_skip_nonfinite_steps)")
    jax.debug.callback(lambda v: _note_nonfinite_host(bool(v)),
                       found_inf)


def _skip_guard_default() -> bool:
    try:
        return bool(GLOBAL_FLAGS.get("skip_nonfinite_steps"))
    except KeyError:  # pragma: no cover - partial installs
        return True


def _defer_probes_default() -> bool:
    """XLA refuses to persist an executable that contains host
    callbacks, so with FLAGS_compile_cache_dir set the step must keep
    its HLO callback-free: the probe signals (anomaly scalars, the
    skip-guard verdict) ride the step's outputs and are drained on the
    host instead of streaming through jax.debug.callback."""
    try:
        return bool(GLOBAL_FLAGS.get("compile_cache_dir"))
    except KeyError:  # pragma: no cover - partial installs
        return False


def inject_fault_mults(batch) -> None:
    """Thread in-graph value faults (testing.faults: nonfinite_grad /
    loss_spike) into a step's batch as scalar multipliers. Keys are
    added on EVERY call while such a spec is armed (value 1.0 when not
    firing), so the compiled signature stays stable — one trace, not
    one per flip."""
    from ..testing import faults as _faults
    if not (_faults.active() and _faults.value_points_armed()):
        return
    batch["grad_mult"] = jnp.float32(
        _faults.value_mult("nonfinite_grad"))
    batch["loss_mult"] = jnp.float32(_faults.value_mult("loss_spike"))


def apply_fault_mults(loss, grads, batch):
    """Traced half of the value-fault injection: multiply the loss /
    every inexact grad leaf by the armed multipliers (1.0 = inert)."""
    if "loss_mult" in batch:
        loss = loss * batch["loss_mult"].astype(loss.dtype)
    if "grad_mult" in batch:
        mult = batch["grad_mult"]
        grads = jax.tree.map(
            lambda g: g * mult.astype(g.dtype)
            if jnp.issubdtype(getattr(g, "dtype", jnp.int32),
                              jnp.inexact) else g, grads)
    return loss, grads


def _wire_param_meta(model, optimizer) -> None:
    """Hand per-parameter ParamAttr metadata (need_clip, regularizer)
    to the optimizer, keyed like param_dict — reference semantics:
    need_clip=False skips grad clip; a param regularizer overrides the
    optimizer-level regularization for that parameter."""
    meta = {}
    for n, p in model.named_parameters():
        need_clip = getattr(p, "need_clip", True)
        reg = getattr(p, "regularizer", None)
        if not need_clip or reg is not None:
            meta[n] = (need_clip, reg)
    if meta:
        optimizer.set_param_meta(meta)

def step_phases(entry, fn: str):
    """One dispatch of the train entry point ``entry`` (``TrainStep``,
    ``ShardedTrainStep``): its own count of calls goes up by one and is
    the step number of the record that the tracer opens for ``fn``, the
    program dispatched. The caller opens the phases on what this
    returns and hands it the step's metrics (``dispatched``)."""
    entry._dispatches += 1
    return _obs.get_tracer().step(fn, entry._dispatches)


class TrainStep:
    """Compile model+loss+optimizer into one donated-state XLA program.

    Replaces the reference's append_backward (backward.py:1215) + optimizer
    op emission + ParallelExecutor run loop for the single-device case.

    Usage::

        step = TrainStep(model, opt, loss_fn)
        for batch in loader:
            loss = step(batch)     # state lives inside, donated each call
    """

    def __init__(self, model: Layer, optimizer: Optimizer,
                 loss_fn: Callable, extra_metrics: Optional[Dict[str,
                 Callable]] = None, seed: int = 0,
                 amp_dtype=None, scaler=None) -> None:
        self.model = model
        self.optimizer = optimizer
        _wire_param_meta(model, optimizer)
        self.loss_fn = loss_fn
        self.extra_metrics = extra_metrics or {}
        # AMP: amp_dtype runs the forward under auto_cast; a GradScaler
        # (fp16) compiles dynamic loss scaling + skip-on-inf into the
        # step (ref: amp_check_finite_and_scale + update_loss_scaling)
        self.amp_dtype = amp_dtype
        if scaler is not None and not scaler.enable:
            scaler = None
        self.scaler = scaler
        # finiteness guard for every precision (bf16/fp32 runs get the
        # skip alone, without scaling); flag read at construction
        self._skip_guard = _skip_guard_default()
        # persistent-cache mode: keep the step HLO callback-free so the
        # executable can be written to / read from FLAGS_compile_cache_dir
        self._defer_probes = _defer_probes_default()
        self._pending_signals = []
        self._dispatches = 0    # the step number of the timeline's records
        # host-LR rescale applied on divergence-rollback re-entry
        # (FLAGS_rollback_lr_factor); changing it retraces once
        self.lr_scale = 1.0
        params = model.param_dict()
        buffers = model.buffer_dict()
        self.state = {
            "params": params,
            "buffers": buffers,
            "opt": optimizer.init(params),
            "rng": _random.make_key(seed),
        }
        if self.scaler is not None:
            self.state["scaler"] = self.scaler.init()
        # jit through the recompile tracker: a shape-churning input
        # pipeline shows up as jit_traces_total{fn=...} growth + a
        # storm warning instead of a silent 100x slowdown
        self._span_name = f"TrainStep({type(model).__name__})"
        # the skip-step guard selects between the updated and the old
        # state; where the compiler finds that program too large for the
        # device, the update runs inside a conditional instead (_call)
        self._guard_in_place = False
        self._jit()

    def _jit(self) -> None:
        self._jitted = _obs.instrumented_jit(
            self._step, self._span_name, donate_argnums=(0,))
        self._jitted_multi = _obs.instrumented_jit(
            self._multi, self._span_name + ".multi", donate_argnums=(0,))

    def _call(self, jitted: str, *args):
        """Run a compiled step. A select over the updated and the old
        state lets XLA copy the old master and moments before their
        in-place write (2.4 GB of a 600M-parameter step, ROADMAP A2(d));
        when that program does not fit the device, the compiler refuses
        it before any buffer is donated, and the step is traced again
        with the update inside one conditional (``amp.guarded_update``),
        which writes the state in place. The select stays the default:
        the conditional's operand layouts cost the hybrid decoder 2% of
        its step (PERF.md section 6, PR 39)."""
        try:
            return getattr(self, jitted)(*args)
        except jax.errors.JaxRuntimeError as e:
            if (self._guard_in_place or "RESOURCE_EXHAUSTED" not in str(e)
                    or not (self._skip_guard or self.scaler is not None)
                    or any(x.is_deleted() for x in jax.tree.leaves(
                        self.state) if isinstance(x, jax.Array))):
                raise
        warnings.warn(f"{self._span_name}: the skip-step guard's select "
                      f"does not fit the device; the optimizer's update "
                      f"runs inside a conditional instead")
        self._guard_in_place = True
        self._jit()
        return getattr(self, jitted)(*args)

    def _step(self, state, batch):
        import contextlib

        from .. import amp as _amp
        params = state["params"]
        buffers = state["buffers"]
        rng, step_key = jax.random.split(state["rng"])
        scaler = self.scaler if "scaler" in state else None

        def loss_of(p):
            ctx = _amp.auto_cast(enable=True, dtype=self.amp_dtype) \
                if self.amp_dtype is not None \
                else contextlib.nullcontext()
            with ctx, _random.rng_scope(default=step_key,
                                        dropout=step_key):
                out, new_buffers = functional_call(
                    self.model, p, buffers, *batch["args"],
                    capture_buffers=True, **batch.get("kwargs", {}))
                loss = self.loss_fn(out, *batch["labels"])
            if scaler is not None:
                loss = scaler.scale(loss, state["scaler"])
            return loss, (new_buffers, out)

        (loss, (new_buffers, out)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        loss, grads = apply_fault_mults(loss, grads, batch)
        # finiteness: the scaler's unscale fuses the check; bare runs
        # get the check alone (skip-step guard)
        found_inf = None
        if scaler is not None:
            grads, found_inf = scaler.unscale(grads, state["scaler"])
            loss = loss / state["scaler"]["scale"].astype(loss.dtype)
        elif self._skip_guard:
            found_inf = ~_amp.all_finite(grads)
        deferred = {}
        if _obs.enabled():
            # anomaly sentinel: NaN/Inf + spike watch on the loss and
            # the gradient global norm. Default: async host callbacks
            # baked in at trace time (observe_traced semantics, no
            # per-step sync). In persistent-cache mode the scalars ride
            # the step outputs instead and are drained host-side — a
            # callback in the HLO would make the executable uncacheable.
            # pt.probe: what metrics being on costs on the device
            with jax.named_scope("pt.probe"):
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads)
                    if jnp.issubdtype(getattr(g, "dtype", jnp.int32),
                                      jnp.inexact)) + 0.0)
                if self._defer_probes:
                    deferred["_pt_gnorm"] = gnorm
                else:
                    _obs.anomaly.probe("loss", loss)
                    _obs.anomaly.probe("grad_norm", gnorm)
        lr = batch.get("lr")
        if "lr_scale" in batch:
            # rollback LR rescale: reproduce the LR apply_gradients
            # would have used and multiply — works for floats,
            # in-graph schedulers (traced over the step counter) and
            # host-driven schedulers (batch["lr"]) alike
            from ..optimizer.lr import resolve_lr
            base = lr if lr is not None else resolve_lr(
                self.optimizer.learning_rate, state["opt"]["step"] + 1)
            lr = base * batch["lr_scale"]
        def update():
            return self.optimizer.apply_gradients(
                params, grads, state["opt"], lr_override=lr)

        if found_inf is not None and self._guard_in_place:
            new_params, new_opt = _amp.guarded_update(
                found_inf, update, (params, state["opt"]))
        else:
            new_params, new_opt = update()
        if found_inf is not None:
            # skip-step: discard the whole update in-graph — params,
            # optimizer slots (incl. the step counter, matching the
            # reference's update_loss_scaling) and buffer stats
            if not self._guard_in_place:
                new_params = _amp.select_update(found_inf, new_params,
                                                params)
                new_opt = _amp.select_update(found_inf, new_opt,
                                             state["opt"])
            new_buffers = _amp.select_update(found_inf, new_buffers,
                                             buffers)
            if self._defer_probes and _obs.enabled():
                deferred["_pt_nonfinite"] = found_inf
            else:
                probe_nonfinite(found_inf)
        metrics = {"loss": loss}
        for name, fn in self.extra_metrics.items():
            metrics[name] = fn(out, *batch["labels"])
        metrics.update(deferred)
        new_state = {"params": new_params, "buffers": new_buffers,
                     "opt": new_opt, "rng": rng}
        if scaler is not None:
            new_state["scaler"] = scaler.update(state["scaler"],
                                                found_inf)
        return (new_state, metrics)

    def _multi(self, state, batches, lr):
        # iterations-per-loop: K optimizer steps inside ONE compiled
        # program (TF TPU's iterations_per_loop / t5x steps_per_loop).
        # On remote-dispatch backends each dispatch pays per-buffer
        # runtime copies (profiled ~19% of the BERT step, README); a
        # lax.scan amortizes that over K steps while keeping RNG/step
        # semantics identical to K sequential calls (the body is the
        # same _step; parity-tested in test_train_step_multi).
        def body(st, xs):
            if lr is not None:
                xs = dict(xs, lr=lr)
            return self._step(st, xs)

        return jax.lax.scan(body, state, batches)

    def _make_batch(self, args, labels, kwargs):
        from ..parallel.spmd import inject_host_lr
        batch = inject_host_lr(
            {"args": args, "labels": as_label_tuple(labels),
             "kwargs": kwargs}, self.optimizer)
        inject_fault_mults(batch)
        if self.lr_scale != 1.0:
            batch["lr_scale"] = jnp.float32(self.lr_scale)
        return batch

    # One record a dispatch in the tracer's step timeline, its three
    # host phases the spans pt/train_step/{make_batch,dispatch,drain}
    # on a profile's clock (docs/observability.md): a device-idle gap
    # is charged to batch building, to the dispatch (host batch
    # transfer included) or to the drain, which reads device buffers.
    # One cached-bool check a call while metrics are off.

    def __call__(self, *args, labels=(), **kwargs):
        phases = step_phases(self, self._span_name)
        with phases.phase("make_batch"):
            batch = self._make_batch(args, labels, kwargs)
        with phases.phase("dispatch", fn=self._span_name):
            self.state, metrics = self._call("_jitted", self.state, batch)
        phases.dispatched(metrics)
        with phases.phase("drain"):
            return self._drain_signals(metrics)

    def run_steps(self, *args, labels=(), **kwargs):
        """Run K fused optimizer steps in one dispatch: every leaf of
        ``args``/``labels``/``kwargs`` carries a leading steps axis K
        (stack K per-step batches). Returns metrics whose leaves are
        stacked [K] (``metrics["loss"][-1]`` is the latest). A host-LR
        scheduler's live value is held constant across the K steps of
        one dispatch (scheduler granularity becomes K steps)."""
        from ..parallel.spmd import host_lr_of
        phases = step_phases(self, self._span_name + ".multi")
        with phases.phase("make_batch"):
            batch = {"args": args, "labels": as_label_tuple(labels),
                     "kwargs": kwargs}
            lr = host_lr_of(self.optimizer)
            lr = None if lr is None else jnp.float32(lr)
        with phases.phase("dispatch", fn=self._span_name + ".multi"):
            self.state, metrics = self._call("_jitted_multi", self.state,
                                             batch, lr)
        phases.dispatched(metrics, stacked=True)
        with phases.phase("drain"):
            return self._drain_signals(metrics)

    # -- persistent-cache probe drain ------------------------------------
    # With FLAGS_compile_cache_dir set the step's anomaly/skip-guard
    # signals come back as reserved "_pt_*" metric leaves instead of
    # jax.debug.callback (a host callback in the HLO disqualifies the
    # executable from the persistent cache). The drain feeds them to
    # the exact host handlers the callbacks would have hit, reading a
    # value only once its buffer is ready — still no forced sync on
    # the hot path; anything left over is flushed at sync_to_model.

    def _drain_signals(self, metrics):
        nf = metrics.pop("_pt_nonfinite", None)
        gn = metrics.pop("_pt_gnorm", None)
        if nf is not None or gn is not None:
            self._pending_signals.append((nf, gn, metrics.get("loss")))
            self.flush_signals(block=False)
        return metrics

    def flush_signals(self, block: bool = True) -> None:
        """Deliver pending deferred probe signals to their host-side
        handlers (anomaly sentinel, nonfinite-step counter). With
        ``block=False`` only values whose buffers are already on the
        host are consumed; the rest stay queued."""
        keep = []
        for item in self._pending_signals:
            if not block and not all(
                    getattr(v, "is_ready", lambda: True)()
                    for v in item if v is not None):
                keep.append(item)
                continue
            nf, gn, loss = item
            if nf is not None:
                for _ in range(int(np.sum(np.asarray(nf, dtype=bool)))):
                    _note_nonfinite_host(True)
            if gn is not None:
                # [K]-stacked leaves from run_steps flatten to K samples
                # in step order; scalars from __call__ to one
                sent = _obs.anomaly.sentinel()
                if loss is not None:
                    for x in np.ravel(np.asarray(loss,
                                                 dtype=np.float64)):
                        sent.observe("loss", float(x))
                for x in np.ravel(np.asarray(gn, dtype=np.float64)):
                    sent.observe("grad_norm", float(x))
        self._pending_signals = keep

    def compiled_hlo(self, *args, labels=(), **kwargs) -> str:
        """Optimized-HLO text of the whole train step for these inputs
        (no execution; state is NOT consumed). Backs structural perf
        analysis: counting copy/transpose ops or collectives before
        spending chip time."""
        batch = self._make_batch(args, labels, kwargs)
        return self._jitted.lower(self.state, batch).compile().as_text()

    def reset_from_model(self) -> None:
        """Re-pull params/buffers from the eager model (the model is the
        source of truth at program boundaries; users may have set_value'd
        or loaded weights since the last compile).

        Optimizer slots (momenta etc.) are intentionally carried over so
        fit(); fit() continues training; for a fresh optimizer pair this
        with ``self.state["opt"] = self.optimizer.init(params)``."""
        self.state["params"] = self.model.param_dict()
        self.state["buffers"] = self.model.buffer_dict()

    # sync trained state back into the eager model
    def sync_to_model(self) -> None:
        self.flush_signals()
        state = {**self.state["params"], **self.state["buffers"]}
        # A step that failed mid-execution may have consumed (deleted) the
        # donated buffers with no result to replace them; those weights are
        # unrecoverable — skip them rather than raise from cleanup paths.
        alive = {k: v for k, v in state.items()
                 if not (hasattr(v, "is_deleted") and v.is_deleted())}
        if len(alive) < len(state):
            warnings.warn(
                f"sync_to_model: {len(state) - len(alive)} donated buffers "
                "were lost to a failed step; those weights keep their "
                "previous values in the eager model")
        self.model.set_state_dict(alive, strict=False)

    @property
    def params(self):
        return self.state["params"]


class EvalStep:
    """Jitted inference step (no grad, eval-mode buffers frozen)."""

    def __init__(self, model: Layer,
                 metric_fns: Optional[Dict[str, Callable]] = None) -> None:
        self.model = model
        self.metric_fns = metric_fns or {}
        self._span_name = f"EvalStep({type(model).__name__})"
        self._jitted = _obs.instrumented_jit(self._step, self._span_name)

    def _step(self, params, buffers, batch):
        was_training = self.model.training
        self.model.eval()
        try:
            out = functional_call(self.model, params, buffers,
                                  *batch["args"])
        finally:
            if was_training:
                self.model.train()
        metrics = {name: fn(out, *batch["labels"])
                   for name, fn in self.metric_fns.items()}
        return out, metrics

    def __call__(self, params, buffers, *args, labels=()):
        batch = {"args": args, "labels": as_label_tuple(labels)}
        if _obs.enabled():
            with _obs.span(self._span_name):
                return self._jitted(params, buffers, batch)
        return self._jitted(params, buffers, batch)


# ---------------------------------------------------------------------------
# program_guard-era helpers (thin parity shims)
# ---------------------------------------------------------------------------

def data(name: str, shape: Sequence[int], dtype="float32"):
    """Placeholder declaration (ref: fluid.data). Returns a spec used for
    documentation/validation; programs take feeds by name at run time."""
    from ..core.dtype import convert_dtype
    return jax.ShapeDtypeStruct(
        tuple(s if s and s > 0 else 1 for s in shape), convert_dtype(dtype))


def default_main_program():
    raise NotImplementedError(
        "program construction is tracing in the TPU design: wrap your "
        "computation in a function and build a Program(fn) "
        "(see paddle_tpu.static.Program)")
