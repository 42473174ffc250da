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


def test_routed_kernels_run_per_shard_under_a_mesh(monkeypatch):
    """GSPMD cannot partition a Mosaic kernel, so under the mesh a
    sharded step sets, flash attention and layer norm run inside a
    shard_map on their local block (batch over dp, heads over mp) and
    give what the unsharded call gives, values and gradients."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu import kernels
    from paddle_tpu.kernels import flash_attention as fa_mod
    from paddle_tpu.kernels import layer_norm as ln_mod

    blocks = []

    def interpreted(name, orig):
        def call(*a, **k):
            blocks.append((name, a[0].shape))
            k.pop("interpret", None)
            return orig(*a, interpret=True, **k)
        return call

    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa_mod, "flash_attention",
                        interpreted("flash", fa_mod.flash_attention))
    monkeypatch.setattr(ln_mod, "layer_norm_pallas",
                        interpreted("ln", ln_mod.layer_norm_pallas))

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
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("dp", "mp"))
        heads = NamedSharding(mesh, P("dp", None, "mp", None))
        with jax.sharding.set_mesh(mesh):
            got_attn = attn_g(*(jax.device_put(a, heads)
                                for a in (q, k, v)))
            got_norm = norm_g(
                jax.device_put(x, NamedSharding(mesh, P("dp"))), w, b)
    finally:
        pt.set_flags(saved)
    # each kernel saw its local block, not the global array
    assert {s for n, s in blocks if n == "flash"} == {(2, 64, 2, 128)}
    assert {s for n, s in blocks if n == "ln"} == {(4, 16, 128)}
    for got, want in ((got_attn, want_attn), (got_norm, want_norm)):
        for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                       rtol=2e-5, atol=2e-5)
