"""Automatic mixed precision.

TPU-native redesign of the reference's AMP stack (static rewrite:
/root/reference/python/paddle/fluid/contrib/mixed_precision/decorator.py:218
+ fp16_utils.py white/black-list casting + update_loss_scaling :169; eager:
paddle/fluid/imperative/amp_auto_cast.cc:87; the finiteness op
operators/amp/amp_check_finite_and_scale_op.cc).

On TPU the native low precision is **bfloat16**: same exponent range as
fp32, so loss scaling is unnecessary — ``auto_cast`` simply runs whitelisted
ops in bf16. fp16-style dynamic loss scaling (:class:`GradScaler`) is kept
for API/capability parity and for fp16 experiments; its entire
check-finite + scale-update logic compiles into the train step (the
reference runs it as separate graph ops).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..core.dtype import convert_dtype

# ops that benefit from low precision (matmul/conv MXU ops)
WHITE_LIST = {"matmul", "mul", "conv2d", "conv3d", "bmm", "einsum", "linear"}
# ops that must stay fp32 (reductions, norms, softmax, exp)
BLACK_LIST = {"softmax", "log_softmax", "cross_entropy", "layer_norm",
              "batch_norm", "mean", "sum", "exp", "log"}


class _AmpState(threading.local):
    def __init__(self) -> None:
        self.enabled = False
        self.dtype = jnp.bfloat16
        self.level = "O1"


_amp_state = _AmpState()


@contextlib.contextmanager
def auto_cast(enable: bool = True, dtype="bfloat16", level: str = "O1",
              custom_white_list=None, custom_black_list=None):
    """(ref: amp_guard, dygraph/amp/auto_cast.py:90)."""
    prev = (_amp_state.enabled, _amp_state.dtype, _amp_state.level)
    _amp_state.enabled = enable
    _amp_state.dtype = convert_dtype(dtype)
    _amp_state.level = level
    try:
        yield
    finally:
        _amp_state.enabled, _amp_state.dtype, _amp_state.level = prev


amp_guard = auto_cast


def amp_enabled() -> bool:
    return _amp_state.enabled


def amp_dtype():
    return _amp_state.dtype


def cast_model_to_low_precision(model, dtype="bfloat16"):
    """O2-style whole-model cast (ref: fp16_utils cast_model_to_fp16)."""
    return model.to(dtype=dtype)


def low_precision_policy(x, op_name: str = "matmul"):
    """Cast an input per white/black list when amp is active."""
    if not _amp_state.enabled:
        return x
    if op_name in BLACK_LIST:
        return x.astype(jnp.float32) if x.dtype == _amp_state.dtype else x
    if op_name in WHITE_LIST and jnp.issubdtype(x.dtype, jnp.floating):
        return x.astype(_amp_state.dtype)
    return x


@jax.named_scope("pt.guard")
def all_finite(tree) -> jax.Array:
    """Scalar bool: every floating leaf of ``tree`` is finite — the
    check half of the reference's amp_check_finite_and_scale op,
    usable standalone (the bf16/fp32 skip-step guard). Integer leaves
    (sparse RowSlices rows, step counters) are ignored."""
    checks = []
    for g in jax.tree.leaves(tree):
        dt = getattr(g, "dtype", None)
        if dt is not None and jnp.issubdtype(dt, jnp.inexact):
            checks.append(jnp.all(jnp.isfinite(g)))
    if not checks:
        return jnp.asarray(True)
    return jnp.all(jnp.stack(checks))


@jax.named_scope("pt.guard")
def select_update(found_inf, updated, current):
    """Per-leaf ``where(found_inf, current, updated)`` over two
    same-structure pytrees: the skip-step half of the reference's AMP
    stack, compiled into the train step — no host sync, the whole
    update is discarded in-graph when the step saw non-finite grads."""
    return jax.tree.map(
        lambda u, c: jnp.where(found_inf, c, u), updated, current)


class GradScaler:
    """Dynamic loss scaling (ref: loss_scaler.py:27 AmpScaler;
    update rule: update_loss_scaling op — incr every
    ``incr_every_n_steps`` clean steps, decr after n nan steps).

    Functional usage inside a jitted step::

        scaler_state = scaler.init()
        scaled_loss = scaler.scale(loss, scaler_state)
        grads = ...  # grads of scaled loss
        grads, found_inf = scaler.unscale(grads, scaler_state)
        new_params = where(found_inf, params, updated_params)
        scaler_state = scaler.update(scaler_state, found_inf)
    """

    def __init__(self, enable: bool = True,
                 init_loss_scaling: float = 2.0 ** 15,
                 incr_ratio: float = 2.0, decr_ratio: float = 0.5,
                 incr_every_n_steps: int = 1000,
                 decr_every_n_nan_or_inf: int = 2) -> None:
        self.enable = enable
        self.init_loss_scaling = init_loss_scaling
        self.incr_ratio = incr_ratio
        self.decr_ratio = decr_ratio
        self.incr_every_n_steps = incr_every_n_steps
        self.decr_every_n_nan_or_inf = decr_every_n_nan_or_inf

    def init(self) -> Dict[str, Any]:
        return {
            "scale": jnp.asarray(self.init_loss_scaling, jnp.float32),
            "good_steps": jnp.zeros((), jnp.int32),
            "bad_steps": jnp.zeros((), jnp.int32),
        }

    def scale(self, loss, state):
        if not self.enable:
            return loss
        return loss * state["scale"].astype(loss.dtype)

    def unscale(self, grads, state):
        """Returns (unscaled_grads, found_inf) — the
        amp_check_finite_and_scale op fused in."""
        if not self.enable:
            return grads, jnp.zeros((), bool)
        inv = 1.0 / state["scale"]
        unscaled = jax.tree.map(
            lambda g: g * inv.astype(g.dtype)
            if jnp.issubdtype(getattr(g, "dtype", jnp.int32),
                              jnp.inexact) else g, grads)
        found_inf = ~all_finite(unscaled)
        return unscaled, found_inf

    def update(self, state, found_inf):
        if not self.enable:
            return state
        good = jnp.where(found_inf, 0, state["good_steps"] + 1)
        bad = jnp.where(found_inf, state["bad_steps"] + 1, 0)
        scale = state["scale"]
        # increase after n good steps
        incr = good >= self.incr_every_n_steps
        scale = jnp.where(incr, scale * self.incr_ratio, scale)
        good = jnp.where(incr, 0, good)
        # decrease after n bad steps
        decr = bad >= self.decr_every_n_nan_or_inf
        scale = jnp.where(decr, jnp.maximum(scale * self.decr_ratio, 1.0),
                          scale)
        bad = jnp.where(decr, 0, bad)
        return {"scale": scale, "good_steps": good, "bad_steps": bad}

    # eager-style helpers (dygraph AmpScaler parity)
    def minimize(self, *args, **kwargs):
        raise NotImplementedError(
            "use the functional scale/unscale/update inside a TrainStep")


def decorate(optimizer, amp_lists=None, init_loss_scaling: float = 2.0 ** 15,
             use_dynamic_loss_scaling: bool = True):
    """(ref: decorator.py:218) returns (optimizer, GradScaler)."""
    scaler = GradScaler(enable=use_dynamic_loss_scaling,
                        init_loss_scaling=init_loss_scaling)
    return optimizer, scaler
