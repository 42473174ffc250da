"""Rules that keep a chip run honest: where the compile cache lives is
decided from outside, and a TPU backend never quietly runs a Pallas
kernel under the interpreter."""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# both entry points, the flag one with a directory of its own to offer
_CACHE_PROBE = """
import jax
import paddle_tpu as pt
from paddle_tpu.sysconfig import (apply_compile_cache_flag,
                                  enable_compile_cache)
enable_compile_cache()
first = jax.config.jax_compilation_cache_dir
enable_compile_cache("/elsewhere/explicit")
explicit = jax.config.jax_compilation_cache_dir
pt.set_flags({"compile_cache_dir": "/elsewhere/flag"})
apply_compile_cache_flag()
print("DIRS", first, explicit, jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("env_dir", ["/from/the/environment", None],
                         ids=["env-set", "env-unset"])
def test_compile_cache_dir_is_placed_from_outside(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("FLAGS_compile_cache_dir", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    dirs = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("DIRS ")][-1].split()[1:]
    if env_dir:
        # no code path sets another directory, whatever it is handed
        assert dirs == [env_dir] * 3
    else:
        # the fixed checkout path by default; an explicit directory or
        # the flag's still applies when the environment names none
        assert dirs == [os.path.join(ROOT, ".jax_cache"),
                        "/elsewhere/explicit", "/elsewhere/flag"]


@pytest.mark.parametrize("on_tpu", [True, False], ids=["tpu", "cpu"])
def test_paged_attention_interprets_only_off_tpu(monkeypatch, on_tpu):
    """Routing follows the BACKEND alone: with FLAGS_use_pallas_kernels
    off a TPU must still compile the kernel (there is no XLA
    composition to fall back to), never interpret it."""
    import paddle_tpu as pt
    from paddle_tpu import kernels
    from paddle_tpu.kernels import paged_attention as pa

    seen = []

    def spy(name):
        def record(*args, interpret, **kwargs):
            seen.append((name, interpret))
            return np.zeros(())
        return record

    monkeypatch.setattr(pa, "paged_attention", spy("decode"))
    monkeypatch.setattr(pa, "paged_attention_multiquery", spy("verify"))
    monkeypatch.setattr(kernels, "_on_tpu", lambda: on_tpu)
    pt.set_flags({"use_pallas_kernels": False})
    try:
        kernels.maybe_paged_attention(None, None, None, None, None)
        kernels.maybe_paged_attention_multiquery(None, None, None, None,
                                                 None, None)
    finally:
        pt.set_flags({"use_pallas_kernels": True})
    assert seen == [("decode", not on_tpu), ("verify", not on_tpu)]


def _interpret_routed_kernels(monkeypatch, blocks):
    """Route to the Pallas kernels as a TPU backend would and run them
    under the interpreter; ``blocks`` collects (kernel, shape of the
    block one call saw)."""
    from paddle_tpu import kernels
    from paddle_tpu.kernels import flash_attention as fa_mod
    from paddle_tpu.kernels import layer_norm as ln_mod

    flash, ln_forward = fa_mod.flash_attention, ln_mod._ln_forward

    def flash_interpreted(*a, **k):
        blocks.append(("flash", a[0].shape))
        k.pop("interpret", None)
        return flash(*a, interpret=True, **k)

    def ln_interpreted(x, w, b, eps, interpret):
        blocks.append(("ln", x.shape))
        return ln_forward(x, w, b, eps, True)

    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa_mod, "flash_attention", flash_interpreted)
    monkeypatch.setattr(ln_mod, "_ln_forward", ln_interpreted)


def _dp2mp2():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))


def test_routed_kernels_run_per_shard_under_a_mesh(monkeypatch):
    """GSPMD cannot partition a Mosaic kernel, so under the mesh a
    sharded step sets, flash attention and layer norm run inside a
    shard_map on their local block (batch over dp, heads over mp) and
    give what the unsharded call gives, values and gradients."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu import kernels

    blocks = []
    _interpret_routed_kernels(monkeypatch, blocks)

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (4, 64, 4, 128)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(rng.random((4, 1, 1, 64)) > 0.2)
    x = jnp.asarray(rng.normal(0, 1, (8, 16, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(1, 0.1, (128,)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.1, (128,)), jnp.float32)

    def attn(q, k, v):
        out = kernels.maybe_flash_attention(q, k, v, mask=mask,
                                            layout="bthd")
        return jnp.sum(out * out), out

    def norm(x, w, b):
        out = kernels.maybe_layer_norm(x, w, b, 1e-5, 2)
        return jnp.sum(out * out), out

    attn_g = jax.jit(jax.value_and_grad(attn, argnums=(0, 1, 2),
                                        has_aux=True))
    norm_g = jax.jit(jax.value_and_grad(norm, argnums=(0, 1, 2),
                                        has_aux=True))
    saved = pt.get_flags(["flash_attention_min_seq"])
    pt.set_flags({"flash_attention_min_seq": 64})
    try:
        want_attn, want_norm = attn_g(q, k, v), norm_g(x, w, b)
        assert {s for n, s in blocks if n == "flash"} == {(4, 64, 4, 128)}
        blocks.clear()
        mesh = _dp2mp2()
        heads = NamedSharding(mesh, P("dp", None, "mp", None))
        with jax.sharding.set_mesh(mesh):
            got_attn = attn_g(*(jax.device_put(a, heads)
                                for a in (q, k, v)))
            got_norm = norm_g(
                jax.device_put(x, NamedSharding(mesh, P("dp"))), w, b)
    finally:
        pt.set_flags(saved)
    # each kernel saw its local block, not the global array (the norm
    # kernel takes rows: 8 x 16 of them, halved over dp)
    assert {s for n, s in blocks if n == "flash"} == {(2, 64, 2, 128)}
    assert {s for n, s in blocks if n == "ln"} == {(64, 128)}
    for got, want in ((got_attn, want_attn), (got_norm, want_norm)):
        for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                       rtol=2e-5, atol=2e-5)


def _shard_map_bodies(jaxpr):
    """Text of the body of every ``shard_map`` equation in a jaxpr,
    those inside other equations' bodies included."""
    import jax
    bodies = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            bodies.append(str(eqn.params["jaxpr"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            bodies += _shard_map_bodies(sub)
    return bodies


@pytest.mark.parametrize("kernel", ["layer_norm", "flash"])
def test_grad_of_a_per_shard_kernel_sums_nothing_over_an_axis_it_is_whole_on(
        monkeypatch, kernel):
    """Layer norm's rows are split over dp and whole over mp. Were the
    kernel differentiated inside its shard_map, the transpose would
    ``psum`` the rows' cotangent over mp: two identical halves added
    over the link, 50 MB a norm site at the four-chip cell's shape. The
    ``custom_vjp`` sits outside: the forward kernel is still per shard,
    the backward is plain XLA and the jaxpr holds no psum at all (the
    dw/db sums over dp are the partitioner's). Flash attention is split
    over both axes (batch over dp, heads over mp) and whole on none: its
    forward and backward kernels each run inside a shard_map, and no
    cotangent is summed over either axis."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import kernels

    _interpret_routed_kernels(monkeypatch, [])

    def norm(x, w, b):
        out = kernels.maybe_layer_norm(x, w, b, 1e-5, 2)
        return jnp.sum(out * out)

    def attn(q, k, v):
        out = kernels.maybe_flash_attention(q, k, v, layout="bthd")
        return jnp.sum(out * out)

    if kernel == "layer_norm":
        fn, inside = norm, ["layer_norm_fwd"]
        args = (jnp.ones((8, 16, 128)), jnp.ones((128,)), jnp.ones((128,)))
    else:
        fn, inside = attn, ["flash_fwd", "flash_bwd"]
        args = (jnp.ones((4, 64, 4, 128)),) * 3
    saved = pt.get_flags(["flash_attention_min_seq"])
    pt.set_flags({"flash_attention_min_seq": 64})
    try:
        with jax.sharding.set_mesh(_dp2mp2()):
            closed = jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2)))(*args)
    finally:
        pt.set_flags(saved)
    bodies = _shard_map_bodies(closed.jaxpr)
    for name in inside:
        assert any(name in body for body in bodies), (name, len(bodies))
    assert "psum" not in str(closed)


def _two_encoder_layers():
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.nn.layer import functional_call

    pt.seed(7)
    net = nn.TransformerEncoder(
        lambda: nn.TransformerEncoderLayer(512, 4, 1024, dropout=0.0,
                                           activation="gelu"), 2)
    net.train()
    params, buffers = net.param_dict(), net.buffer_dict()

    def loss(p, x):
        y = functional_call(net, p, buffers, x)
        return jnp.sum(y * jnp.cos(y))

    return params, loss


def test_two_encoder_layers_on_dp2mp2_give_the_unsharded_gradients(
        monkeypatch):
    """What guards the input gradient on the mesh path: two stacked
    encoder layers (the second's input gradient is the first's output
    cotangent), parameters placed by megatron_param_rule on dp2 x mp2,
    kernels interpreted per shard. The gradient of the input and of
    every parameter of the first layer is what the unsharded program
    gives, through the norm's hoisted backward and the q/k/v input
    gradients summed before the exchange; the gauge counts the two
    attention sites that took the summed projections."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu.parallel.spmd import megatron_param_rule

    blocks = []
    _interpret_routed_kernels(monkeypatch, blocks)
    params, loss = _two_encoder_layers()
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (4, 64, 512)),
                    jnp.float32)
    name = "two_encoder_layers_dp2mp2"
    grads = obs.instrumented_jit(jax.grad(loss, argnums=(0, 1)), name)
    saved = pt.get_flags(["flash_attention_min_seq_train",
                          "enable_metrics"])
    pt.set_flags({"flash_attention_min_seq_train": 64,
                  "enable_metrics": True})
    try:
        want_p, want_x = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)
        assert {s for n, s in blocks if n == "flash"} == {(4, 64, 4, 128)}
        blocks.clear()
        mesh = _dp2mp2()
        rule = megatron_param_rule()
        placed = {k: jax.device_put(v, NamedSharding(mesh, rule(k, v)))
                  for k, v in params.items()}
        assert placed["layers.0.self_attn.q_proj.weight"] \
            .sharding.spec == P(None, "mp")
        with jax.sharding.set_mesh(mesh):
            got_p, got_x = grads(
                placed, jax.device_put(x, NamedSharding(mesh, P("dp"))))
        summed = obs.gauge("pt_qkv_grad_summed_sites").value(fn=name)
    finally:
        pt.set_flags(saved)
    assert summed == 2
    assert {s for n, s in blocks if n == "flash"} == {(2, 64, 2, 128)}
    assert {s for n, s in blocks if n == "ln"} == {(128, 512)}
    first = [k for k in params if k.startswith("layers.0.")]
    assert len(first) == 16      # 6 Linear and 2 LayerNorm, weight + bias
    # one absolute scale for the parameters: k_proj.bias has no
    # gradient (softmax ignores a shift of every key) and reads noise
    scale = max(float(jnp.max(jnp.abs(want_p[k]))) for k in first)
    for k in first:
        np.testing.assert_allclose(
            np.asarray(got_p[k]), np.asarray(want_p[k]), rtol=1e-4,
            atol=1e-5 * scale, err_msg=k)
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x),
                               rtol=1e-4, atol=1e-5 * float(
                                   jnp.max(jnp.abs(want_x))))


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
def test_summed_gradient_projections_are_three_linear_calls_forward(bias):
    """``_qkv_linear`` changes only the backward: its three outputs are
    bitwise what three ``Linear`` calls give, in bf16 as the cell runs
    them, eagerly and compiled; its gradients are theirs."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import nn
    from paddle_tpu.nn.layers.transformer import _qkv_linear

    rng = np.random.default_rng(2)
    projs = [nn.Linear(128, 128, bias_attr=None if bias else False)
             for _ in range(3)]
    for proj in projs:
        proj.to(dtype="bfloat16")
        if bias:
            proj.set_state_dict({"bias": jnp.asarray(
                rng.normal(0, 1, (128,)), jnp.bfloat16)}, strict=False)
    x = jnp.asarray(rng.normal(0, 1, (2, 16, 128)), jnp.bfloat16)
    wb = [p for proj in projs for p in (proj.weight, proj.bias)]

    def plain(x, *wb):
        return tuple(nn.functional.linear(x, w, b)
                     for w, b in zip(wb[::2], wb[1::2]))

    for run in (lambda f: f, jax.jit):
        for got, want in zip(run(_qkv_linear)(x, *wb), run(plain)(x, *wb)):
            assert got.dtype == want.dtype == jnp.bfloat16
            assert np.array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))

    def scalar(f):
        return lambda x, *wb: sum(
            jnp.sum(o.astype(jnp.float32) * (i + 1.0))
            for i, o in enumerate(f(x, *wb)))

    args = tuple(i for i, a in enumerate((x, *wb)) if a is not None)
    got = jax.grad(scalar(_qkv_linear), argnums=args)(x, *wb)
    want = jax.grad(scalar(plain), argnums=args)(x, *wb)
    for g, w_ in zip(got, want):
        assert g.dtype == w_.dtype and g.shape == w_.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w_, np.float32),
                                   rtol=2e-2, atol=2e-2 * float(
                                       jnp.max(jnp.abs(w_))))
    # weight and bias gradients are F.linear's own, to the bit
    for g, w_ in zip(got[1:], want[1:]):
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(w_, np.float32))


def test_without_a_mesh_attention_traces_three_plain_projections():
    """The one-chip program must not change: with no mp axis in scope
    ``MultiHeadAttention`` calls its three ``Linear`` layers, no
    ``custom_vjp`` round them, and the gauge reads 0."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu import observability as obs
    from paddle_tpu.nn.layer import functional_call

    attn = nn.MultiHeadAttention(64, 4)
    attn.eval()
    params, buffers = attn.param_dict(), attn.buffer_dict()
    x = jnp.ones((2, 8, 64))

    def fwd(p, x):
        return functional_call(attn, p, buffers, x)

    closed = jax.make_jaxpr(fwd)(params, x)
    text = str(closed)
    assert "custom_vjp" not in text
    leaves, _ = jax.tree.flatten(params)
    names = sorted(params)          # a dict flattens in key order
    assert len(leaves) == len(names)
    by_var = dict(zip(closed.jaxpr.invars, names))
    dots = [by_var[v] for eqn in closed.jaxpr.eqns
            if eqn.primitive.name == "dot_general"
            for v in eqn.invars if v in by_var]
    assert dots == ["q_proj.weight", "k_proj.weight", "v_proj.weight",
                    "out_proj.weight"]

    name = "attention_without_a_mesh"
    was = obs.enabled()
    pt.set_flags({"enable_metrics": True})
    try:
        obs.instrumented_jit(fwd, name)(params, x)
        assert obs.gauge("pt_qkv_grad_summed_sites").value(fn=name) == 0
    finally:
        pt.set_flags({"enable_metrics": was})


def test_sharded_step_counts_its_summed_attention_sites():
    """The gauge beside ``pt_dropout_mask_sites``: a 12-layer BERT
    through ``ShardedTrainStep`` on dp2 x mp2 takes the summed
    projections at each of its 12 attention sites (traced from abstract
    values, nothing run); a retrace replaces the count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   pretraining_loss)
    from paddle_tpu.parallel import (ShardedTrainStep, create_mesh,
                                     megatron_param_rule)

    b, s, pred = 8, 32, 4
    config = BertConfig(vocab_size=512, hidden_size=64,
                        num_attention_heads=4, intermediate_size=128,
                        max_position_embeddings=s)
    was = obs.enabled()
    pt.set_flags({"enable_metrics": True})
    try:
        step = ShardedTrainStep(
            BertForPretraining(config), pt.optimizer.AdamW(1e-5),
            pretraining_loss,
            create_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4]),
            batch_spec=P("dp"), param_rule=megatron_param_rule())

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), step.state)
        batch = {"args": (i32(b, s),), "labels": (i32(b, pred), i32(b)),
                 "kwargs": {"masked_positions": i32(b, pred)}}
        for _ in range(2):
            with jax.sharding.set_mesh(step.mesh):
                step._jitted.trace(state, batch)
            jax.clear_caches()
        summed = obs.gauge("pt_qkv_grad_summed_sites").value(
            fn=step._span_name)
    finally:
        pt.set_flags({"enable_metrics": was})
    assert summed == config.num_hidden_layers == 12
