"""Optimizers.

TPU-native redesign of the reference's optimizer family
(/root/reference/paddle/fluid/operators/optimizers/: sgd_op.cc,
momentum_op.cc, lars_momentum_op.cc, adam_op.cc/adam_op.h, adamax_op.cc,
adagrad_op.cc, adadelta_op.cc, rmsprop_op.cc, ftrl_op.cc, lamb_op.cc,
dpsgd_op.cc + python/paddle/fluid/optimizer.py:55). In the reference each
optimizer is a graph op mutating params in a scope; here each is a pure
``(params, grads, state, step) -> (new_params, new_state)`` transform that
compiles INTO the jitted train step with donated buffers — the in-graph
update capability, the XLA way. The stateful ``step()`` method gives eager
(dygraph) parity on an attached Layer.

Sparse RowSlices grads (ops/sparse.py, SelectedRows analogue) get row-wise
updates for SGD/Adagrad/Momentum (lazy-mode semantics of the reference's
selected-rows kernels, adam_op.h:473).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.random import make_key
from ..nn.layer import Layer, Parameter
from ..ops.sparse import RowSlices, scatter_apply, to_dense
from . import lr as lr_module
from .lr import LRScheduler, resolve_lr


def _tree_map(fn, *trees):
    return jax.tree.map(fn, *trees,
                        is_leaf=lambda x: isinstance(x, RowSlices))


def _moment_dtype(default):
    """Storage dtype for Adam-family moments: FLAGS_optimizer_moment_
    dtype=bfloat16 halves the m/v HBM traffic (update math stays fp32;
    fp32 masters unaffected)."""
    from ..flags import GLOBAL_FLAGS
    val = GLOBAL_FLAGS.get("optimizer_moment_dtype")
    if val == "bfloat16":
        return jnp.bfloat16
    if val != "float32":
        # a typo'd value silently measuring the fp32 baseline would
        # corrupt exactly the A/B evidence this flag exists to produce
        raise ValueError(
            f"optimizer_moment_dtype={val!r}: expected 'float32' or "
            "'bfloat16'")
    return default


def _as_f32(x):
    """Upcast a low-precision leaf to fp32 for optimizer math.

    Master-weight semantics of the reference's AMP path
    (/root/reference/python/paddle/fluid/contrib/mixed_precision/decorator.py):
    update math always runs in fp32 even when params/grads are bf16/fp16;
    apply_gradients casts the result back to the param's own dtype.
    """
    dtype = getattr(x, "dtype", None)
    if dtype in (jnp.bfloat16, jnp.float16):
        return x.astype(jnp.float32)
    return x


class Optimizer:
    """Base optimizer.

    Functional protocol (used by jitted train steps):
      state = opt.init(params)
      new_params, new_state = opt.apply_gradients(params, grads, state)

    Eager protocol (dygraph parity):
      opt = Adam(parameters=model.parameters()); loss_grads = ...;
      opt.step(grads)  # or attach via set_grads then step()
    """

    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay: Optional[float] = None, grad_clip=None,
                 name: Optional[str] = None,
                 regularization=None) -> None:
        self.learning_rate = learning_rate
        self._parameter_list = list(parameters) if parameters else None
        # the reference's ``regularization=L2Decay(...)`` spelling is an
        # alias for weight_decay; both floats and regularizer objects
        # (called as reg(param, grad)) are accepted either way
        if weight_decay is None and regularization is not None:
            weight_decay = regularization
        self.weight_decay = weight_decay
        self.grad_clip = grad_clip
        self._eager_state = None
        # per-parameter ParamAttr metadata (set_param_meta): {name:
        # (need_clip, regularizer)}; consumed when grads/params are
        # name-keyed dicts (the TrainStep contract)
        self._param_meta: Dict[str, Any] = {}

    def set_param_meta(self, meta) -> None:
        """Record per-parameter ParamAttr metadata: ``{name:
        (need_clip, regularizer)}``. need_clip=False excludes that
        parameter from grad_clip; a per-param regularizer replaces the
        optimizer-level weight_decay for that parameter (reference
        semantics: ParamAttr.regularizer overrides optimizer
        regularization)."""
        self._param_meta = dict(meta)

    def _with_zeroed_attr(self, attr: str, fn):
        """Run ``fn`` with ``self.<attr>`` temporarily set to 0.0 —
        trace-time only (the per-leaf loop is sequential Python), used
        by name-filtered decay exclusions."""
        saved = getattr(self, attr)
        setattr(self, attr, 0.0)
        try:
            return fn()
        finally:
            setattr(self, attr, saved)

    def _decay_grad(self, g, p32, reg=None):
        """Apply weight decay to a grad: per-param regularizer if set,
        else the optimizer-level weight_decay (float coefficient or a
        regularizer object called as reg(param, grad))."""
        wd = reg if reg is not None else self.weight_decay
        if not wd:
            return g
        if callable(wd):
            return wd(p32, g)
        return g + wd * p32

    # ------------------------------------------------------------------
    # functional API
    # ------------------------------------------------------------------
    def init(self, params) -> Dict[str, Any]:
        # Slots always live in fp32 regardless of param dtype (bf16 moment
        # buffers diverge); update math runs in fp32 and the new param is
        # cast back to its own dtype — see apply_gradients. This also keeps
        # the train state's dtypes fixed across steps (a dtype that drifts
        # bf16->fp32 between calls forces jit recompiles).
        #
        # Low-precision params additionally get a persistent fp32 MASTER
        # copy in their slots: without it, p32 - lr*u rounds back to the
        # old bf16 value whenever the update is below half an ulp (~0.4%
        # relative for bf16), silently freezing training. The master
        # accumulates sub-ulp updates; the bf16 param is its cast-down view
        # (reference AMP master weights: contrib/mixed_precision/
        # decorator.py _create_master_weight).
        def mk(p):
            slots = dict(self.init_slots(_as_f32(p)))
            if getattr(p, "dtype", None) in (jnp.bfloat16, jnp.float16):
                slots["master"] = jnp.asarray(p, jnp.float32)
            return slots

        slots = _tree_map(mk, params)
        return {"step": jnp.zeros((), jnp.int32), "slots": slots}

    def init_slots(self, p) -> Dict[str, jax.Array]:
        return {}

    # device scope (docs/observability.md): clip, regularisation, the
    # update and the master-to-parameter cast read as one block
    @jax.named_scope("pt.optimizer")
    def apply_gradients(self, params, grads, state,
                        lr_override=None) -> Tuple[Any, Dict[str, Any]]:
        step = state["step"] + 1
        lr_t = lr_override if lr_override is not None \
            else resolve_lr(self.learning_rate, step)
        # Upcast grads BEFORE clip/decay: a global-norm clip in fp16
        # overflows (sum of squares vs fp16 max 65504) and silently zeroes
        # every grad; all optimizer math is fp32 (master weights). This is
        # the single upcast site for grads — the loop below only upcasts p.
        def _g32(g):
            if g is None:
                return None
            if isinstance(g, RowSlices):
                return RowSlices(g.rows, _as_f32(g.values), g.dense_rows)
            return _as_f32(g)

        grads = jax.tree.map(
            _g32, grads,
            is_leaf=lambda x: x is None or isinstance(x, RowSlices))
        meta = self._param_meta if isinstance(grads, dict) else {}
        has_name_filter = \
            getattr(self, "apply_decay_param_fun", None) is not None or \
            getattr(self, "exclude_fn", None) is not None
        if has_name_filter and not isinstance(params, dict):
            # positional pytrees name leaves "[0]", "[1].bias", ... —
            # a name filter would silently mis-apply decay (same hazard
            # the eager step() guard refuses)
            raise NotImplementedError(
                "apply_decay_param_fun / exclude_from_weight_decay_fn "
                "need name-keyed dict params (the TrainStep contract)")
        flat_p, treedef = jax.tree.flatten(
            params, is_leaf=lambda x: isinstance(x, RowSlices))
        flat_g = treedef.flatten_up_to(grads)
        flat_s = treedef.flatten_up_to(state["slots"])
        need_names = bool(meta) or has_name_filter
        if need_names:
            # align per-leaf regularizers/names with the flat order via
            # the actual tree paths (works for nested dicts too;
            # unmatched paths just get defaults)
            from jax.tree_util import tree_flatten_with_path
            paths, _ = tree_flatten_with_path(
                params, is_leaf=lambda x: isinstance(x, RowSlices))
            names = [".".join(str(getattr(k, "key", k)) for k in path)
                     for path, _leaf in paths]
            regs = [meta.get(n, (True, None))[1] for n in names]
        else:
            names = [None] * len(flat_p)
            regs = [None] * len(flat_p)

        if self.grad_clip is not None:
            no_clip = {n for n, (nc, _) in meta.items() if not nc}
            if no_clip:  # implies meta, hence need_names
                # excluded params keep their raw grads and do not feed
                # the (global) norm (ref: ParamAttr need_clip=False);
                # clipping runs on an index-keyed flat view so nesting
                # cannot hide an exclusion
                sub = {i: g for i, (g, n) in
                       enumerate(zip(flat_g, names)) if n not in no_clip}
                if sub:  # all-excluded: nothing to clip
                    clipped = self.grad_clip(sub)
                    flat_g = [clipped.get(i, g)
                              for i, g in enumerate(flat_g)]
            else:
                flat_g = treedef.flatten_up_to(self.grad_clip(grads))

        new_p, new_s = [], []
        for p, g, s, r, n in zip(flat_p, flat_g, flat_s, regs, names):
            np_, ns_ = self._update_leaf(p, g, s, lr_t, step, reg=r,
                                         name=n)
            new_p.append(np_)
            new_s.append(ns_)
        return (jax.tree.unflatten(treedef, new_p),
                {"step": step, "slots": jax.tree.unflatten(treedef, new_s)})

    def _update_leaf(self, p, g, s, lr_t, step, reg=None, name=None):
        """One leaf's update: fp32 master handling, RowSlices dispatch,
        decay, cast back to the param dtype."""
        if g is None:
            return p, s
        out_dtype = getattr(p, "dtype", None)
        # fp32 master copy (see init): the update reads and writes the
        # master; the low-precision param is its cast-down view.
        has_master = isinstance(s, dict) and "master" in s
        p32 = s["master"] if has_master else _as_f32(p)
        s_upd = {k: v for k, v in s.items() if k != "master"} \
            if has_master else s
        if isinstance(g, RowSlices):
            np_, ns_ = self.update_sparse(p32, g, s_upd, lr_t, step)
        else:
            g = self._decay_grad(g, p32, reg)
            np_, ns_ = self.update(p32, g, s_upd, lr_t, step)
        if has_master:
            ns_ = dict(ns_, master=np_)
        if out_dtype is not None and np_.dtype != out_dtype:
            np_ = np_.astype(out_dtype)
        return np_, ns_

    def update(self, p, g, slots, lr_t, step):
        raise NotImplementedError

    def update_sparse(self, p, g: RowSlices, slots, lr_t, step):
        # default: densify (correct, not bandwidth-optimal)
        return self.update(p, to_dense(g), slots, lr_t, step)

    # ------------------------------------------------------------------
    # eager API
    # ------------------------------------------------------------------
    def _eager_params(self) -> Dict[int, Parameter]:
        if self._parameter_list is None:
            raise ValueError(
                "pass parameters= to the optimizer for eager step()")
        return {i: p for i, p in enumerate(self._parameter_list)
                if p.trainable}

    def step(self, grads: Optional[Sequence[jax.Array]] = None) -> None:
        params = self._eager_params()
        if grads is None:
            raise ValueError("eager step() needs grads aligned with "
                             "the optimizer's parameter list")
        if self._param_meta or \
                getattr(self, "apply_decay_param_fun", None) is not None \
                or getattr(self, "exclude_fn", None) is not None:
            # eager grads are index-keyed, so name filters would match
            # nothing and silently mis-apply decay — refuse instead
            raise NotImplementedError(
                "name-based decay/clip filters (ParamAttr metadata, "
                "apply_decay_param_fun, exclude_from_weight_decay_fn) "
                "need name-keyed grads; train through TrainStep or call "
                "apply_gradients with a name-keyed dict")
        values = {i: p.value for i, p in params.items()}
        gdict = {i: g for (i, _), g in zip(params.items(), grads)}
        if self._eager_state is None:
            self._eager_state = self.init(values)
        new_values, self._eager_state = self.apply_gradients(
            values, gdict, self._eager_state)
        for i, p in params.items():
            p.value = new_values[i]
        from ..observability import metrics as _obs_metrics
        if _obs_metrics.enabled():
            _obs_metrics.counter("optimizer_steps_total",
                                 "optimizer update steps applied").inc()

    def clear_grad(self) -> None:
        pass  # grads are values, not state, in the functional design

    def get_lr(self) -> float:
        if isinstance(self.learning_rate, LRScheduler):
            return self.learning_rate.get_lr()
        return float(self.learning_rate)

    def set_lr(self, value: float) -> None:
        self.learning_rate = value

    def state_dict(self):
        return self._eager_state or {}

    def set_state_dict(self, state) -> None:
        self._eager_state = state

    # reference-style one-call minimize for eager models
    def minimize(self, loss_fn: Callable, model: Layer):
        params = model.param_dict()
        buffers = model.buffer_dict()

        def lf(p):
            from ..nn.layer import functional_call
            out, new_buf = functional_call(model, p, buffers,
                                           capture_buffers=True)
            return out, new_buf

        raise NotImplementedError(
            "use paddle_tpu.static.TrainStep or jax.value_and_grad with "
            "apply_gradients; minimize() of arbitrary closures is not "
            "supported in the functional design")


class SGD(Optimizer):
    """(ref: sgd_op.cc)."""

    def update(self, p, g, slots, lr_t, step):
        return p - lr_t * g.astype(p.dtype), slots

    def update_sparse(self, p, g: RowSlices, slots, lr_t, step):
        return scatter_apply(p, g, lambda rows, vals:
                             rows - lr_t * vals.astype(p.dtype)), slots


class Momentum(Optimizer):
    """(ref: momentum_op.cc; use_nesterov attr)."""

    def __init__(self, learning_rate=0.001, momentum: float = 0.9,
                 use_nesterov: bool = False, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def init_slots(self, p):
        return {"velocity": jnp.zeros_like(p)}

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        v = self.momentum * slots["velocity"] + g
        if self.use_nesterov:
            new_p = p - lr_t * (g + self.momentum * v)
        else:
            new_p = p - lr_t * v
        return new_p, {"velocity": v}


class LarsMomentum(Optimizer):
    """(ref: lars_momentum_op.cc) layer-adaptive rate scaling."""

    def __init__(self, learning_rate=0.001, momentum: float = 0.9,
                 lars_coeff: float = 0.001, lars_weight_decay: float = 0.0005,
                 epsilon: float = 1e-9, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.momentum = momentum
        self.lars_coeff = lars_coeff
        self.lars_weight_decay = lars_weight_decay
        self.epsilon = epsilon

    def init_slots(self, p):
        return {"velocity": jnp.zeros_like(p)}

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = lr_t * self.lars_coeff * p_norm / (
            g_norm + self.lars_weight_decay * p_norm + self.epsilon)
        local_lr = jnp.where(p_norm > 0, local_lr, lr_t)
        v = self.momentum * slots["velocity"] \
            + local_lr * (g + self.lars_weight_decay * p)
        return p - v, {"velocity": v}


class Adam(Optimizer):
    """(ref: adam_op.h AdamFunctor)."""

    def __init__(self, learning_rate=0.001, beta1: float = 0.9,
                 beta2: float = 0.999, epsilon: float = 1e-8,
                 lazy_mode: bool = False, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_mode = lazy_mode

    def init_slots(self, p):
        return {"m": jnp.zeros(p.shape, _moment_dtype(p.dtype)),
                "v": jnp.zeros(p.shape, _moment_dtype(p.dtype))}

    def _bias_correct_lr(self, lr_t, step):
        step_f = step.astype(jnp.float32)
        bc1 = 1.0 - jnp.power(self.beta1, step_f)
        bc2 = 1.0 - jnp.power(self.beta2, step_f)
        return lr_t * jnp.sqrt(bc2) / bc1

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        # moments may be STORED low-precision (FLAGS_optimizer_moment_
        # dtype): math always runs fp32, storage casts back
        m_dt, v_dt = slots["m"].dtype, slots["v"].dtype
        m = self.beta1 * _as_f32(slots["m"]) + (1 - self.beta1) * g
        v = self.beta2 * _as_f32(slots["v"]) \
            + (1 - self.beta2) * jnp.square(g)
        lr_c = self._bias_correct_lr(lr_t, step)
        new_p = p - lr_c * m / (jnp.sqrt(v) + self.epsilon)
        return new_p, {"m": m.astype(m_dt), "v": v.astype(v_dt)}

    def update_sparse(self, p, g: RowSlices, slots, lr_t, step):
        if not self.lazy_mode:
            return self.update(p, to_dense(g), slots, lr_t, step)
        # lazy: only touched rows updated (ref: adam_op.h:473 sparse functor)
        lr_c = self._bias_correct_lr(lr_t, step)
        m, v = slots["m"], slots["v"]
        safe_rows = jnp.minimum(g.rows, p.shape[0] - 1)
        valid = (g.rows < p.shape[0])[:, None].astype(p.dtype)
        g_rows = g.values.astype(p.dtype) * valid
        m_rows = self.beta1 * _as_f32(m[safe_rows]) \
            + (1 - self.beta1) * g_rows
        v_rows = self.beta2 * _as_f32(v[safe_rows]) + (1 - self.beta2) \
            * jnp.square(g_rows)
        p_rows = p[safe_rows] - lr_c * m_rows / (jnp.sqrt(v_rows)
                                                 + self.epsilon)
        return (p.at[safe_rows].set(p[safe_rows] * (1 - valid)
                                    + p_rows * valid),
                {"m": m.at[safe_rows].set(
                    (_as_f32(m[safe_rows]) * (1 - valid)
                     + m_rows * valid).astype(m.dtype)),
                 "v": v.at[safe_rows].set(
                    (_as_f32(v[safe_rows]) * (1 - valid)
                     + v_rows * valid).astype(v.dtype))})


class AdamW(Adam):
    """(ref: adamw in optimizer.py — decoupled weight decay)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, weight_decay: float = 0.01,
                 apply_decay_param_fun=None, **kw) -> None:
        if kw.pop("regularization", None) is not None:
            # the base class would fold it into coupled weight_decay,
            # which the next line resets — reject loudly instead of
            # silently training without decay (explicit None is fine)
            raise TypeError(
                "AdamW uses DECOUPLED weight decay: pass weight_decay="
                "<float> (regularization= is the coupled-L2 spelling; "
                "use Adam for that)")
        kw.pop("weight_decay", None)
        super().__init__(learning_rate, beta1, beta2, epsilon, **kw)
        self.decoupled_weight_decay = weight_decay
        self.apply_decay_param_fun = apply_decay_param_fun
        self.weight_decay = None  # decoupled, not L2

    def update(self, p, g, slots, lr_t, step):
        new_p, new_slots = super().update(p, g, slots, lr_t, step)
        new_p = new_p - lr_t * self.decoupled_weight_decay * p
        return new_p, new_slots

    def _update_leaf(self, p, g, s, lr_t, step, reg=None, name=None):
        fn = self.apply_decay_param_fun
        if fn is not None and name is not None and not fn(name):
            # reference: apply_decay_param_fun(name) False => NO decay
            return self._with_zeroed_attr(
                "decoupled_weight_decay",
                lambda: super(AdamW, self)._update_leaf(
                    p, g, s, lr_t, step, reg, name))
        return super()._update_leaf(p, g, s, lr_t, step, reg, name)


class Adamax(Optimizer):
    """(ref: adamax_op.cc)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def init_slots(self, p):
        return {"m": jnp.zeros_like(p), "u": jnp.zeros_like(p)}

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        m = self.beta1 * slots["m"] + (1 - self.beta1) * g
        # the operator keeps epsilon inside the running maximum, so the
        # quotient needs none
        u = jnp.maximum(self.beta2 * slots["u"] + self.epsilon, jnp.abs(g))
        step_f = step.astype(jnp.float32)
        lr_c = lr_t / (1.0 - jnp.power(self.beta1, step_f))
        return p - lr_c * m / u, {"m": m, "u": u}


class Adagrad(Optimizer):
    """(ref: adagrad_op.cc)."""

    def __init__(self, learning_rate=0.001, epsilon: float = 1e-6,
                 initial_accumulator_value: float = 0.0, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.epsilon = epsilon
        self.initial_accumulator_value = initial_accumulator_value

    def init_slots(self, p):
        return {"moment": jnp.full_like(p, self.initial_accumulator_value)}

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        moment = slots["moment"] + jnp.square(g)
        return p - lr_t * g / (jnp.sqrt(moment) + self.epsilon), \
            {"moment": moment}


class Adadelta(Optimizer):
    """(ref: adadelta_op.cc)."""

    def __init__(self, learning_rate=1.0, rho: float = 0.95,
                 epsilon: float = 1e-6, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.rho, self.epsilon = rho, epsilon

    def init_slots(self, p):
        return {"avg_sq_grad": jnp.zeros_like(p),
                "avg_sq_update": jnp.zeros_like(p)}

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        asg = self.rho * slots["avg_sq_grad"] + (1 - self.rho) * jnp.square(g)
        upd = g * jnp.sqrt(slots["avg_sq_update"] + self.epsilon) \
            / jnp.sqrt(asg + self.epsilon)
        asu = self.rho * slots["avg_sq_update"] \
            + (1 - self.rho) * jnp.square(upd)
        return p - lr_t * upd, {"avg_sq_grad": asg, "avg_sq_update": asu}


class RMSProp(Optimizer):
    """(ref: rmsprop_op.cc; centered variant supported)."""

    def __init__(self, learning_rate=0.001, rho: float = 0.95,
                 epsilon: float = 1e-6, momentum: float = 0.0,
                 centered: bool = False, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.rho, self.epsilon = rho, epsilon
        self.momentum_coef = momentum
        self.centered = centered

    def init_slots(self, p):
        s = {"mean_square": jnp.zeros_like(p),
             "moment": jnp.zeros_like(p)}
        if self.centered:
            s["mean_grad"] = jnp.zeros_like(p)
        return s

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        ms = self.rho * slots["mean_square"] + (1 - self.rho) * jnp.square(g)
        new_slots = {"mean_square": ms}
        if self.centered:
            mg = self.rho * slots["mean_grad"] + (1 - self.rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self.epsilon)
            new_slots["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self.epsilon)
        mom = self.momentum_coef * slots["moment"] + lr_t * g / denom
        new_slots["moment"] = mom
        return p - mom, new_slots


class Lamb(Optimizer):
    """(ref: lamb_op.cc) layer-adaptive Adam for large-batch."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay: float = 0.01,
                 beta1: float = 0.9, beta2: float = 0.999,
                 epsilon: float = 1e-6, exclude_from_weight_decay_fn=None,
                 **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lamb_weight_decay = lamb_weight_decay
        self.exclude_fn = exclude_from_weight_decay_fn

    def init_slots(self, p):
        return {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p)}

    def _update_leaf(self, p, g, s, lr_t, step, reg=None, name=None):
        if self.exclude_fn is not None and name is not None \
                and self.exclude_fn(name):
            # reference: exclude_from_weight_decay_fn(name) True =>
            # no lamb weight decay for this parameter
            return self._with_zeroed_attr(
                "lamb_weight_decay",
                lambda: super(Lamb, self)._update_leaf(
                    p, g, s, lr_t, step, reg, name))
        return super()._update_leaf(p, g, s, lr_t, step, reg, name)

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        m = self.beta1 * slots["m"] + (1 - self.beta1) * g
        v = self.beta2 * slots["v"] + (1 - self.beta2) * jnp.square(g)
        step_f = step.astype(jnp.float32)
        m_hat = m / (1.0 - jnp.power(self.beta1, step_f))
        v_hat = v / (1.0 - jnp.power(self.beta2, step_f))
        r = m_hat / (jnp.sqrt(v_hat) + self.epsilon) \
            + self.lamb_weight_decay * p
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
        return p - lr_t * trust * r, {"m": m, "v": v}


class Ftrl(Optimizer):
    """(ref: ftrl_op.cc)."""

    def __init__(self, learning_rate=0.001, l1: float = 0.0,
                 l2: float = 0.0, lr_power: float = -0.5, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.l1, self.l2, self.lr_power = l1, l2, lr_power

    def init_slots(self, p):
        return {"squared": jnp.zeros_like(p), "linear": jnp.zeros_like(p)}

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        sq = slots["squared"]
        new_sq = sq + jnp.square(g)
        sigma = (jnp.power(new_sq, -self.lr_power)
                 - jnp.power(jnp.maximum(sq, 1e-20), -self.lr_power)) / lr_t
        lin = slots["linear"] + g - sigma * p
        quad = jnp.power(new_sq, -self.lr_power) / lr_t + 2 * self.l2
        pre_shrink = (self.l1 * jnp.sign(lin) - lin) / quad
        new_p = jnp.where(jnp.abs(lin) > self.l1, pre_shrink, 0.0)
        return new_p, {"squared": new_sq, "linear": lin}


class Dpsgd(Optimizer):
    """(ref: dpsgd_op.cc) differentially-private SGD: clip + noise."""

    def __init__(self, learning_rate=0.001, clip: float = 10.0,
                 batch_size: float = 16.0, sigma: float = 1.0, seed: int = 0,
                 **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.clip = clip
        self.batch_size = batch_size
        self.sigma = sigma
        self.seed = seed

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        scale = jnp.minimum(1.0, self.clip / jnp.maximum(g_norm, 1e-12))
        g = g * scale
        key = jax.random.fold_in(make_key(self.seed), step)
        noise = self.sigma * self.clip / self.batch_size \
            * jax.random.normal(key, g.shape, g.dtype)
        return p - lr_t * (g + noise), slots


# Reference-era aliases (fluid.optimizer spellings)
SGDOptimizer = SGD
MomentumOptimizer = Momentum
AdamOptimizer = Adam
AdamaxOptimizer = Adamax
AdagradOptimizer = Adagrad
AdadeltaOptimizer = Adadelta
RMSPropOptimizer = RMSProp
LambOptimizer = Lamb
FtrlOptimizer = Ftrl
LarsMomentumOptimizer = LarsMomentum

from .extras import (ExponentialMovingAverage, GradientMerge,  # noqa: E402
                     Lookahead, ModelAverage)  # noqa: F401


class DecayedAdagrad(Optimizer):
    """(ref: decayed_adagrad_op.cc)."""

    def __init__(self, learning_rate=0.001, decay: float = 0.95,
                 epsilon: float = 1e-6, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.decay, self.epsilon = decay, epsilon

    def init_slots(self, p):
        return {"moment": jnp.zeros_like(p)}

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        moment = self.decay * slots["moment"] \
            + (1 - self.decay) * jnp.square(g)
        return p - lr_t * g / (jnp.sqrt(moment) + self.epsilon), \
            {"moment": moment}


class ProximalGD(Optimizer):
    """(ref: proximal_gd_op.cc) SGD with L1/L2 proximal projection."""

    def __init__(self, learning_rate=0.001, l1: float = 0.0,
                 l2: float = 0.0, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.l1, self.l2 = l1, l2

    def init_slots(self, p):
        return {}

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        prox = p - lr_t * g
        new_p = jnp.sign(prox) * jnp.maximum(
            jnp.abs(prox) - lr_t * self.l1, 0.0) / (1.0 + lr_t * self.l2)
        return new_p, {}


class ProximalAdagrad(Optimizer):
    """(ref: proximal_adagrad_op.cc)."""

    def __init__(self, learning_rate=0.001, l1: float = 0.0,
                 l2: float = 0.0, epsilon: float = 1e-10, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.l1, self.l2, self.epsilon = l1, l2, epsilon

    def init_slots(self, p):
        return {"moment": jnp.zeros_like(p)}

    def update(self, p, g, slots, lr_t, step):
        g = g.astype(p.dtype)
        moment = slots["moment"] + jnp.square(g)
        # the gradient step takes the adapted rate, the shrinkage the
        # plain one (the operator's rule)
        prox = p - lr_t * g / (jnp.sqrt(moment) + self.epsilon)
        new_p = jnp.sign(prox) * jnp.maximum(
            jnp.abs(prox) - lr_t * self.l1, 0.0) / (1.0 + lr_t * self.l2)
        return new_p, {"moment": moment}
