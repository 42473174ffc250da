


def test_bf16_moment_storage():
    """FLAGS_optimizer_moment_dtype=bfloat16: moments stored bf16
    (half the optimizer-state traffic), math in fp32 — training
    matches the fp32-moment run closely and state dtypes are bf16."""
    import numpy as np

    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.static import TrainStep

    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (64, 16)).astype(np.float32)
    w = rng.normal(0, 1, (16, 1)).astype(np.float32)
    y = (x @ w + 0.01 * rng.normal(0, 1, (64, 1))).astype(np.float32)

    def run(moment_dtype):
        pt.set_flags({"optimizer_moment_dtype": moment_dtype})
        try:
            pt.seed(0)
            net = pt.nn.Linear(16, 1)
            opt = pt.optimizer.AdamW(learning_rate=1e-2,
                                     weight_decay=0.01)
            step = TrainStep(net, opt,
                             lambda out, t: pt.nn.functional.mse_loss(
                                 out, t))
            losses = [float(step(x, labels=y)["loss"])
                      for _ in range(20)]
            return losses, step.state["opt"]
        finally:
            pt.set_flags({"optimizer_moment_dtype": "float32"})

    base, _ = run("float32")
    lowp, opt_state = run("bfloat16")
    # moments stored bf16
    m_leaves = [s["m"] for s in opt_state["slots"].values()
                if isinstance(s, dict) and "m" in s]
    assert m_leaves and all(a.dtype == jnp.bfloat16 for a in m_leaves)
    # training trajectory close to the fp32-moment run
    np.testing.assert_allclose(lowp, base, rtol=0.05, atol=1e-3)
    assert lowp[-1] < lowp[0] * 0.75



def test_bf16_moments_dense_and_sparse_paths():
    """bf16 moment storage must hold on both Adam paths, dense and lazy
    sparse rows — slot dtypes stay bfloat16 across steps (no fp32 drift
    forcing recompiles) and the updates track the fp32-moment run
    within bf16 rounding."""
    import numpy as np

    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.optimizer import RowSlices

    rng = np.random.default_rng(1)
    params = {"w": jnp.asarray(rng.normal(0, 1, (32, 8)), jnp.float32),
              "b": jnp.asarray(rng.normal(0, 1, (8,)), jnp.float32)}
    grads = {"w": jnp.asarray(rng.normal(0, 1, (32, 8)), jnp.float32),
             "b": jnp.asarray(rng.normal(0, 1, (8,)), jnp.float32)}

    def run(moment_dtype):
        pt.set_flags({"optimizer_moment_dtype": moment_dtype})
        try:
            opt = pt.optimizer.Adam(learning_rate=1e-2)
            state = opt.init(params)
            p = params
            for _ in range(3):
                p, state = opt.apply_gradients(p, grads, state)
            return p, state
        finally:
            pt.set_flags({"optimizer_moment_dtype": "float32"})

    p32, _ = run("float32")
    p16, st16 = run("bfloat16")
    for k in p32:
        np.testing.assert_allclose(
            np.asarray(p16[k]), np.asarray(p32[k]),
            rtol=2e-2, atol=2e-3, err_msg=f"leaf={k}")
    assert st16["slots"]["w"]["m"].dtype == jnp.bfloat16
    assert st16["slots"]["w"]["v"].dtype == jnp.bfloat16

    # lazy sparse rows keep their slot dtype across scatter updates
    pt.set_flags({"optimizer_moment_dtype": "bfloat16"})
    try:
        opt = pt.optimizer.Adam(learning_rate=1e-2, lazy_mode=True)
        emb = {"e": jnp.asarray(rng.normal(0, 1, (16, 4)), jnp.float32)}
        state = opt.init(emb)
        rows = jnp.asarray([1, 5, 9], jnp.int32)
        vals = jnp.asarray(rng.normal(0, 1, (3, 4)), jnp.float32)
        g = {"e": RowSlices(rows, vals, 16)}
        p = emb
        for _ in range(2):
            p, state = opt.apply_gradients(p, g, state)
        assert state["slots"]["e"]["m"].dtype == jnp.bfloat16
        assert state["slots"]["e"]["v"].dtype == jnp.bfloat16
        touched = np.asarray(state["slots"]["e"]["m"])[[1, 5, 9]]
        assert (np.abs(touched) > 0).all()
        untouched = np.asarray(state["slots"]["e"]["m"])[[0, 2, 15]]
        assert (untouched == 0).all()
    finally:
        pt.set_flags({"optimizer_moment_dtype": "float32"})



def test_param_attr_need_clip_and_regularizer():
    """ParamAttr metadata is honored through TrainStep: need_clip=False
    excludes a param from global-norm clipping; a per-param L2Decay
    overrides the optimizer-level weight decay for that param only."""
    import numpy as np

    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.clip import ClipGradByGlobalNorm
    from paddle_tpu.optimizer import SGD

    # --- need_clip: excluded param keeps its raw gradient
    opt = SGD(learning_rate=1.0,
              grad_clip=ClipGradByGlobalNorm(0.1))
    opt.set_param_meta({"b": (False, None)})
    params = {"w": jnp.ones((4,)), "b": jnp.ones((2,))}
    grads = {"w": jnp.full((4,), 3.0), "b": jnp.full((2,), 3.0)}
    state = opt.init(params)
    new_p, _ = opt.apply_gradients(params, grads, state)
    # b's grad is NOT clipped: update is exactly lr*3
    np.testing.assert_allclose(np.asarray(new_p["b"]), 1.0 - 3.0,
                               rtol=1e-6)
    # w's grad IS clipped to global-norm 0.1 over w alone
    w_upd = 1.0 - np.asarray(new_p["w"])
    np.testing.assert_allclose(np.linalg.norm(w_upd), 0.1, rtol=1e-5)

    # --- per-param regularizer overrides optimizer-level decay
    opt2 = SGD(learning_rate=1.0, weight_decay=0.5)
    opt2.set_param_meta({"b": (True, pt.regularizer.L2Decay(0.0))})
    state2 = opt2.init(params)
    zero_g = {"w": jnp.zeros((4,)), "b": jnp.zeros((2,))}
    new_p2, _ = opt2.apply_gradients(params, zero_g, state2)
    # w decayed by 0.5, b's zero-coeff regularizer wins (no decay)
    np.testing.assert_allclose(np.asarray(new_p2["w"]), 0.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_p2["b"]), 1.0, rtol=1e-6)


def test_regularization_object_as_weight_decay():
    """The reference's regularization=L2Decay(c) spelling works, as
    does weight_decay=L2Decay(c): both decay like the float coeff."""
    import numpy as np

    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.optimizer import Momentum

    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.zeros((4,))}

    outs = []
    for kw in ({"weight_decay": 0.1},
               {"weight_decay": pt.regularizer.L2Decay(0.1)},
               {"regularization": pt.regularizer.L2Decay(0.1)}):
        opt = Momentum(learning_rate=1.0, momentum=0.0, **kw)
        st = opt.init(params)
        new_p, _ = opt.apply_gradients(params, grads, st)
        outs.append(np.asarray(new_p["w"]))
    np.testing.assert_allclose(outs[1], outs[0], rtol=1e-6)
    np.testing.assert_allclose(outs[2], outs[0], rtol=1e-6)


def test_param_attr_metadata_through_train_step():
    """End to end: a Layer built with ParamAttr(need_clip=False,
    regularizer=...) trains through TrainStep with the metadata wired
    into the optimizer automatically."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.clip import ClipGradByGlobalNorm
    from paddle_tpu.static import TrainStep

    pt.seed(0)
    net = pt.nn.Linear(
        4, 2,
        weight_attr=pt.ParamAttr(regularizer=pt.regularizer.L2Decay(0.1)),
        bias_attr=pt.ParamAttr(need_clip=False))
    opt = pt.optimizer.SGD(learning_rate=0.1,
                           grad_clip=ClipGradByGlobalNorm(1.0))
    step = TrainStep(net, opt,
                     lambda out, t: pt.nn.functional.mse_loss(out, t))
    assert opt._param_meta, "TrainStep must wire ParamAttr metadata"
    assert "weight" in next(iter(opt._param_meta))  or any(
        "weight" in k for k in opt._param_meta)
    x = np.random.default_rng(0).normal(0, 1, (8, 4)).astype(np.float32)
    y = np.random.default_rng(1).normal(0, 1, (8, 2)).astype(np.float32)
    l0 = float(step(x, labels=y)["loss"])
    l1 = float(step(x, labels=y)["loss"])
    assert l1 < l0



def test_param_meta_edge_cases():
    """All-params-excluded clipping is a no-op (not a crash), per-param
    regularizers align through NESTED dict pytrees, and AdamW rejects
    the coupled regularization= spelling loudly."""
    import numpy as np

    import jax.numpy as jnp
    import paddle_tpu as pt
    import pytest
    from paddle_tpu.clip import ClipGradByGlobalNorm
    from paddle_tpu.optimizer import SGD, AdamW

    opt = SGD(learning_rate=1.0, grad_clip=ClipGradByGlobalNorm(0.1))
    opt.set_param_meta({"w": (False, None), "b": (False, None)})
    p = {"w": jnp.ones((4,)), "b": jnp.ones((2,))}
    g = {"w": jnp.full((4,), 3.0), "b": jnp.full((2,), 3.0)}
    new_p, _ = opt.apply_gradients(p, g, opt.init(p))
    np.testing.assert_allclose(np.asarray(new_p["w"]), -2.0)

    opt2 = SGD(learning_rate=1.0)
    opt2.set_param_meta({"layer.w": (True, pt.regularizer.L2Decay(0.5))})
    p2 = {"layer": {"w": jnp.ones((3,)), "b": jnp.ones((2,))}}
    g2 = {"layer": {"w": jnp.zeros((3,)), "b": jnp.zeros((2,))}}
    np2, _ = opt2.apply_gradients(p2, g2, opt2.init(p2))
    np.testing.assert_allclose(np.asarray(np2["layer"]["w"]), 0.5)
    np.testing.assert_allclose(np.asarray(np2["layer"]["b"]), 1.0)

    with pytest.raises(TypeError):
        AdamW(learning_rate=1e-3,
              regularization=pt.regularizer.L2Decay(0.01))



def test_adamw_apply_decay_param_fun_and_lamb_exclude():
    """AdamW's apply_decay_param_fun (True = decay) and Lamb's
    exclude_from_weight_decay_fn (True = no decay) are honored per
    parameter name — the standard BERT practice of excluding bias and
    LayerNorm params from decay."""
    import numpy as np

    import jax.numpy as jnp
    from paddle_tpu.optimizer import AdamW, Lamb

    params = {"w": jnp.ones((4,)), "bias": jnp.ones((4,))}
    zero_g = {"w": jnp.zeros((4,)), "bias": jnp.zeros((4,))}

    opt = AdamW(learning_rate=1.0, weight_decay=0.1,
                apply_decay_param_fun=lambda n: "bias" not in n)
    new_p, _ = opt.apply_gradients(params, zero_g, opt.init(params))
    np.testing.assert_allclose(np.asarray(new_p["w"]), 0.9, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_p["bias"]), 1.0,
                               rtol=1e-6)

    # filter still in force on the SECOND step (trace-time flip must
    # restore the coefficient between leaves/steps)
    st = opt.init(params)
    p1, st = opt.apply_gradients(params, zero_g, st)
    p2, _ = opt.apply_gradients(p1, zero_g, st)
    np.testing.assert_allclose(np.asarray(p2["w"]), 0.81, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p2["bias"]), 1.0, rtol=1e-6)

    # non-uniform tensors so decay changes the trust-normalized
    # DIRECTION; the excluded leaf must match a zero-decay run exactly
    rng = np.random.default_rng(0)
    pr = {"w": jnp.asarray(rng.normal(1, 0.3, (4,)), jnp.float32),
          "bias": jnp.asarray(rng.normal(1, 0.3, (4,)), jnp.float32)}
    g = {"w": jnp.asarray(rng.normal(0, 0.1, (4,)), jnp.float32),
         "bias": jnp.asarray(rng.normal(0, 0.1, (4,)), jnp.float32)}
    lamb = Lamb(learning_rate=0.001, lamb_weight_decay=0.1,
                exclude_from_weight_decay_fn=lambda n: "bias" in n)
    lamb0 = Lamb(learning_rate=0.001, lamb_weight_decay=0.0)
    lp, _ = lamb.apply_gradients(pr, g, lamb.init(pr))
    lp0, _ = lamb0.apply_gradients(pr, g, lamb0.init(pr))
    np.testing.assert_allclose(np.asarray(lp["bias"]),
                               np.asarray(lp0["bias"]), rtol=1e-6)
    assert not np.allclose(np.asarray(lp["w"]), np.asarray(lp0["w"]))



def test_need_clip_nested_and_eager_guard():
    """need_clip exclusions work through NESTED grad dicts (index-keyed
    flat clipping), AdamW accepts an explicit regularization=None, and
    the eager step() path refuses name filters loudly instead of
    silently mis-applying decay to index-keyed grads."""
    import numpy as np

    import jax.numpy as jnp
    import paddle_tpu as pt
    import pytest
    from paddle_tpu.clip import ClipGradByGlobalNorm
    from paddle_tpu.optimizer import SGD, AdamW

    opt = SGD(learning_rate=1.0, grad_clip=ClipGradByGlobalNorm(0.1))
    opt.set_param_meta({"layer.b": (False, None)})
    p = {"layer": {"w": jnp.ones((4,)), "b": jnp.ones((2,))}}
    g = {"layer": {"w": jnp.full((4,), 3.0), "b": jnp.full((2,), 3.0)}}
    new_p, _ = opt.apply_gradients(p, g, opt.init(p))
    np.testing.assert_allclose(np.asarray(new_p["layer"]["b"]), -2.0)
    w_upd = 1.0 - np.asarray(new_p["layer"]["w"])
    np.testing.assert_allclose(np.linalg.norm(w_upd), 0.1, rtol=1e-5)

    AdamW(learning_rate=1e-3, regularization=None)  # explicit None ok

    opt2 = AdamW(learning_rate=1e-3,
                 apply_decay_param_fun=lambda n: True,
                 parameters=[pt.nn.Parameter(jnp.ones((2,)))])
    with pytest.raises(NotImplementedError):
        opt2.step([jnp.ones((2,))])


# ----------------------------------------------------------------------
# Every deterministic update rule against a plain statement of the rule.
#
# The references below are NumPy float64 and share no code with
# paddle_tpu.optimizer. Each is written from the rule of the operator the
# class's docstring cites, as the operator's kernel and Paddle 1.8's API
# notes state it, or from the paper where the two differ and the paper
# is what the class follows (noted on the rule). A rule is
# ``(initial slots, update)``; ``update(p, g, s, t, lr, h)`` returns the
# new parameter and writes the new slots into ``s``; ``t`` counts from 1.
# ----------------------------------------------------------------------

import numpy as _np  # noqa: E402
import pytest as _pytest  # noqa: E402


def _zeros(*names):
    return lambda p, h: {n: _np.zeros_like(p) for n in names}


def _sgd(p, g, s, t, lr, h):
    # sgd_op: p - lr * g
    return p - lr * g


def _momentum(p, g, s, t, lr, h):
    # momentum_op: v = mu * v + g; p - lr * v, or with use_nesterov
    # p - lr * (g + mu * v)
    s["v"] = h["momentum"] * s["v"] + g
    if h["use_nesterov"]:
        return p - lr * (g + h["momentum"] * s["v"])
    return p - lr * s["v"]


def _lars_momentum(p, g, s, t, lr, h):
    # lars_momentum_op: the rate scaled by |p| / (|g| + wd |p| + eps)
    # where both norms are positive; v = mu * v + local (g + wd p)
    pn, gn = _np.sqrt(_np.sum(p * p)), _np.sqrt(_np.sum(g * g))
    wd = h["lars_weight_decay"]
    local = lr
    if pn > 0 and gn > 0:
        local = lr * h["lars_coeff"] * pn / (gn + wd * pn + h["epsilon"])
    s["v"] = h["momentum"] * s["v"] + local * (g + wd * p)
    return p - s["v"]


def _adam(p, g, s, t, lr, h):
    # adam_op (AdamFunctor): the bias corrections folded into the rate,
    # epsilon added to the UNcorrected sqrt(v)
    b1, b2 = h["beta1"], h["beta2"]
    s["m"] = b1 * s["m"] + (1 - b1) * g
    s["v"] = b2 * s["v"] + (1 - b2) * g * g
    lr_t = lr * _np.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    return p - lr_t * s["m"] / (_np.sqrt(s["v"]) + h["epsilon"])


def _adamw(p, g, s, t, lr, h):
    # Loshchilov & Hutter 2019, algorithm 2, with the schedule
    # multiplier taken as the rate: Adam's step from p, and lr * coeff * p
    # of decay taken from the same p
    return _adam(p, g, s, t, lr, h) - lr * h["weight_decay"] * p


def _adamax(p, g, s, t, lr, h):
    # adamax_op: epsilon inside the running maximum, none in the
    # quotient; only the first moment is bias-corrected
    b1, b2 = h["beta1"], h["beta2"]
    s["m"] = b1 * s["m"] + (1 - b1) * g
    s["u"] = _np.maximum(b2 * s["u"] + h["epsilon"], _np.abs(g))
    return p - lr / (1 - b1 ** t) * s["m"] / s["u"]


def _adagrad(p, g, s, t, lr, h):
    # adagrad_op
    s["acc"] = s["acc"] + g * g
    return p - lr * g / (_np.sqrt(s["acc"]) + h["epsilon"])


def _adadelta(p, g, s, t, lr, h):
    # adadelta_op (Zeiler 2012); the rate scales the step, as the
    # operator's later versions do (1 gives the paper's rule)
    rho, eps = h["rho"], h["epsilon"]
    s["g2"] = rho * s["g2"] + (1 - rho) * g * g
    step = -_np.sqrt((s["d2"] + eps) / (s["g2"] + eps)) * g
    s["d2"] = rho * s["d2"] + (1 - rho) * step * step
    return p + lr * step


def _rmsprop(p, g, s, t, lr, h):
    # rmsprop_op, centered: the mean gradient's square leaves the
    # mean square under the root
    rho = h["rho"]
    s["ms"] = rho * s["ms"] + (1 - rho) * g * g
    s["mg"] = rho * s["mg"] + (1 - rho) * g
    s["mom"] = h["momentum"] * s["mom"] + lr * g / _np.sqrt(
        s["ms"] - s["mg"] ** 2 + h["epsilon"])
    return p - s["mom"]


def _lamb(p, g, s, t, lr, h):
    # You et al. 2020, algorithm 2 (lamb_op since it corrects the
    # moments' bias): r = m^ / (sqrt(v^) + eps) + wd p; the step is
    # scaled by |p| / |r| where both are positive
    b1, b2 = h["beta1"], h["beta2"]
    s["m"] = b1 * s["m"] + (1 - b1) * g
    s["v"] = b2 * s["v"] + (1 - b2) * g * g
    r = (s["m"] / (1 - b1 ** t)) / (
        _np.sqrt(s["v"] / (1 - b2 ** t)) + h["epsilon"]) \
        + h["lamb_weight_decay"] * p
    pn, rn = _np.sqrt(_np.sum(p * p)), _np.sqrt(_np.sum(r * r))
    trust = pn / rn if pn > 0 and rn > 0 else 1.0
    return p - lr * trust * r


def _ftrl(p, g, s, t, lr, h):
    # ftrl_op (McMahan et al. 2013, per coordinate)
    l1, l2, power = h["l1"], h["l2"], -h["lr_power"]
    new = s["n"] + g * g
    s["z"] = s["z"] + g - (new ** power - s["n"] ** power) / lr * p
    s["n"] = new
    shrunk = (l1 * _np.sign(s["z"]) - s["z"]) / (new ** power / lr + 2 * l2)
    return _np.where(_np.abs(s["z"]) > l1, shrunk, 0.0)


def _decayed_adagrad(p, g, s, t, lr, h):
    # decayed_adagrad_op
    s["acc"] = h["decay"] * s["acc"] + (1 - h["decay"]) * g * g
    return p - lr * g / (_np.sqrt(s["acc"]) + h["epsilon"])


def _shrink(prox, lr, h):
    return _np.sign(prox) * _np.maximum(_np.abs(prox) - lr * h["l1"], 0.0) \
        / (1.0 + lr * h["l2"])


def _proximal_gd(p, g, s, t, lr, h):
    # proximal_gd_op
    return _shrink(p - lr * g, lr, h)


def _proximal_adagrad(p, g, s, t, lr, h):
    # proximal_adagrad_op: the gradient step takes the adapted rate, the
    # shrinkage the plain one
    s["acc"] = s["acc"] + g * g
    return _shrink(p - lr * g / _np.sqrt(s["acc"]), lr, h)


_EPS = 1e-3     # large enough that a misplaced epsilon moves a result

# name: (hyper-parameters, initial slots, rule)
_PLAIN_RULES = {
    "SGD": ({}, _zeros(), _sgd),
    "Momentum": ({"momentum": 0.9, "use_nesterov": False},
                 _zeros("v"), _momentum),
    "LarsMomentum": ({"momentum": 0.9, "lars_coeff": 0.1,
                      "lars_weight_decay": 0.05, "epsilon": _EPS},
                     _zeros("v"), _lars_momentum),
    "Adam": ({"beta1": 0.9, "beta2": 0.99, "epsilon": _EPS},
             _zeros("m", "v"), _adam),
    "AdamW": ({"beta1": 0.9, "beta2": 0.99, "epsilon": _EPS,
               "weight_decay": 0.1}, _zeros("m", "v"), _adamw),
    "Adamax": ({"beta1": 0.9, "beta2": 0.99, "epsilon": _EPS},
               _zeros("m", "u"), _adamax),
    "Adagrad": ({"epsilon": _EPS, "initial_accumulator_value": 0.1},
                lambda p, h: {"acc": _np.full_like(
                    p, h["initial_accumulator_value"])}, _adagrad),
    "Adadelta": ({"rho": 0.9, "epsilon": _EPS},
                 _zeros("g2", "d2"), _adadelta),
    "RMSProp": ({"rho": 0.9, "epsilon": _EPS, "momentum": 0.5,
                 "centered": True}, _zeros("ms", "mg", "mom"), _rmsprop),
    "Lamb": ({"beta1": 0.9, "beta2": 0.99, "epsilon": _EPS,
              "lamb_weight_decay": 0.1}, _zeros("m", "v"), _lamb),
    "Ftrl": ({"l1": 0.05, "l2": 0.1, "lr_power": -0.5},
             _zeros("n", "z"), _ftrl),
    "DecayedAdagrad": ({"decay": 0.9, "epsilon": _EPS},
                       _zeros("acc"), _decayed_adagrad),
    "ProximalGD": ({"l1": 0.05, "l2": 0.1}, _zeros(), _proximal_gd),
    "ProximalAdagrad": ({"l1": 0.05, "l2": 0.1}, _zeros("acc"),
                        _proximal_adagrad),
}


def _leaves(seed, scale, dtype):
    """The three-leaf tree (a matrix, a vector, a table) at ``dtype``,
    as jax arrays and as the float64 values those arrays hold."""
    import jax.numpy as jnp
    rng = _np.random.default_rng(seed)
    tree = {k: jnp.asarray(rng.normal(0, scale, shape), dtype)
            for k, shape in (("w", (4, 3)), ("b", (3,)), ("emb", (6, 3)))}
    return tree, {k: _np.asarray(v, _np.float64) for k, v in tree.items()}


@_pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@_pytest.mark.parametrize("name", sorted(_PLAIN_RULES))
def test_update_matches_plain_rule(name, dtype):
    """Five jitted ``apply_gradients`` steps, a fresh gradient each,
    against the rule in float64. float32 parameters hold to float32
    arithmetic; bfloat16 parameters (float32 masters inside the state)
    to the rounding of the result to bfloat16."""
    import jax
    import paddle_tpu as pt

    hyper, init, rule = _PLAIN_RULES[name]
    lr = 0.05
    opt = getattr(pt.optimizer, name)(learning_rate=lr, **hyper)
    params, want = _leaves(0, 1.0, dtype)
    slots = {k: init(v, hyper) for k, v in want.items()}
    state = opt.init(params)
    step = jax.jit(opt.apply_gradients)
    for t in range(1, 6):
        grads, g64 = _leaves(t, 0.1, dtype)
        params, state = step(params, grads, state)
        want = {k: rule(want[k], g64[k], slots[k], t, lr, hyper)
                for k in want}
    assert int(state["step"]) == 5
    for k, ref in want.items():
        assert str(params[k].dtype) == dtype
        got = _np.asarray(params[k], _np.float64)
        if dtype == "float32":
            _np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6,
                                        err_msg=f"{name} {k}")
        else:
            # half a unit in the last of bfloat16's 8 places, and the
            # float32 master's own error where the result sits on a tie
            _np.testing.assert_allclose(got, ref, rtol=2 ** -8 + 1e-4,
                                        atol=1e-6, err_msg=f"{name} {k}")
            master = _np.asarray(state["slots"][k]["master"], _np.float64)
            _np.testing.assert_allclose(master, ref, rtol=2e-5, atol=2e-6,
                                        err_msg=f"{name} {k} master")


def test_leaf_without_gradient_is_bitwise_untouched():
    """A leaf whose gradient is ``None`` is frozen: decoupled decay must
    not move it, nor its slots, while the other leaves train."""
    import jax.numpy as jnp
    import paddle_tpu as pt

    opt = pt.optimizer.AdamW(learning_rate=0.01, weight_decay=0.1)
    params, start = _leaves(0, 1.0, "float32")
    state = opt.init(params)
    for t in range(1, 4):
        grads, _ = _leaves(t, 0.1, "float32")
        params, state = opt.apply_gradients(params, dict(grads, b=None),
                                            state)
    _np.testing.assert_array_equal(_np.asarray(params["b"], _np.float64),
                                   start["b"])
    for slot in state["slots"]["b"].values():
        _np.testing.assert_array_equal(_np.asarray(slot),
                                       jnp.zeros_like(slot))
    assert not _np.allclose(_np.asarray(params["w"]), start["w"])


def test_leaf_frozen_then_unfrozen_continues_from_unmoved_slots():
    """Two steps without a gradient leave a leaf's moments where they
    were: once unfrozen it moves as the rule says from those moments
    (the bias corrections follow the optimizer's step count, which
    does advance)."""
    import paddle_tpu as pt

    hyper, init, rule = _PLAIN_RULES["Adam"]
    lr = 0.01
    opt = pt.optimizer.Adam(learning_rate=lr, **hyper)
    params, want = _leaves(0, 1.0, "float32")
    slots = {k: init(v, hyper) for k, v in want.items()}
    state = opt.init(params)
    for t, frozen in enumerate((False, True, True, False), start=1):
        grads, g64 = _leaves(t, 0.1, "float32")
        if frozen:
            grads = dict(grads, b=None)
            before = {n: _np.asarray(v) for n, v in
                      state["slots"]["b"].items()}
        params, state = opt.apply_gradients(params, grads, state)
        for k in want:
            if not (frozen and k == "b"):
                want[k] = rule(want[k], g64[k], slots[k], t, lr, hyper)
        if frozen:
            for n, v in state["slots"]["b"].items():
                _np.testing.assert_array_equal(_np.asarray(v), before[n])
    for k, ref in want.items():
        _np.testing.assert_allclose(_np.asarray(params[k], _np.float64),
                                    ref, rtol=2e-5, atol=2e-6, err_msg=k)
