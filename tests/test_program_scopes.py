"""The train step names its own work: ``pt.<block>`` device scopes where
the work is defined, a ``name=`` on every Pallas call with the work a
call must do noted beside it, ``pt/train_step/*`` host spans on the
whole entry point, and ``xprof.op_scopes`` to join a profile's
operations back to their scope (docs/observability.md)."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.models import (BertConfig, BertForPretraining,
                               pretraining_loss)
from paddle_tpu.observability import xprof
from paddle_tpu.static import TrainStep

SCOPES = ("pt.embed", "pt.attn", "pt.ffn", "pt.head_loss", "pt.guard",
          "pt.probe", "pt.optimizer")
# differentiated blocks: forward under jvp(, backward under transpose(jvp(
MODEL_SCOPES = ("pt.embed", "pt.attn", "pt.ffn", "pt.head_loss")
SPANS = ["pt/train_step/make_batch", "pt/train_step/dispatch",
         "pt/train_step/drain"]

# What may stay outside every scope, by op_name: the step's RNG split,
# the negation of the guard's verdict, the program's parameters (named
# by their argument), slices of them, the bare reducers XLA builds and
# the broadcasts its SPMD partitioner adds.
UNSCOPED_OK = re.compile(
    r"^(jit\(_step\)/(jit\(_threefry_split\)|slice|not|squeeze"
    r"|jit\(_unstack\)|convert_element_type|broadcast_in_dim)"
    r"|state\[|batch\["
    r"|(reduce_(sum|and|max|min|or)|add|and|or|max|scatter-add"
    r"|broadcast\.\d+|region_\d+.*)$)")


@pytest.fixture(autouse=True, scope="module")
def _names_in_the_cache_key():
    """JAX leaves ``op_name`` metadata out of the persistent cache's
    key: a cache shared with a checkout whose scopes differ would hand
    these tests that checkout's executable, names and all."""
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    yield
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      was)


@pytest.fixture
def metrics_on():
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    yield
    pt.set_flags({"enable_metrics": False})
    obs.reset_all()


def _bert(layers=2):
    return BertForPretraining(BertConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=layers,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16))


def _batch():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, (8, 16)).astype(np.int32)
    pos = rng.integers(0, 16, (8, 3)).astype(np.int32)
    mlm = rng.integers(0, 128, (8, 3)).astype(np.int32)
    nsp = rng.integers(0, 2, (8,)).astype(np.int32)
    return ids, pos, mlm, nsp


def _run(step, n=2):
    ids, pos, mlm, nsp = _batch()
    for _ in range(n):
        loss = step(ids, labels=(mlm, nsp), masked_positions=pos)["loss"]
    return float(loss)


def _block(op_name):
    found = re.findall(r"pt\.[a-z_]+", op_name)
    return found[-1] if found else None


def _make_step(kind):
    if kind == "TrainStep":
        return TrainStep(_bert(), pt.optimizer.AdamW(1e-3),
                         pretraining_loss)
    from paddle_tpu.parallel import ShardedTrainStep, create_mesh
    return ShardedTrainStep(_bert(), pt.optimizer.AdamW(1e-3),
                            pretraining_loss,
                            create_mesh({"dp": 4, "mp": 2}))


@pytest.fixture(params=["TrainStep", "ShardedTrainStep"])
def traced_step(request, metrics_on):
    step = _make_step(request.param)
    assert np.isfinite(_run(step))
    return step, xprof.op_scopes(step._span_name)


def test_every_scope_occurs_forward_and_backward(traced_step):
    step, scopes = traced_step
    assert scopes, "op_scopes gave nothing"
    by_block = {}
    for op_name in scopes.values():
        by_block.setdefault(_block(op_name), []).append(op_name)
    for scope in SCOPES:
        assert scope in by_block, (scope, sorted(map(str, by_block)))
    for scope in MODEL_SCOPES:
        names = by_block[scope]
        assert any("transpose(jvp(" in n for n in names), scope
        assert any("jvp(" in n and "transpose(" not in n
                   for n in names), scope


def test_what_has_no_scope_is_a_small_listed_set(traced_step):
    _, scopes = traced_step
    bare = sorted({n for n in scopes.values()
                   if n and _block(n) is None})
    unlisted = [n for n in bare if not UNSCOPED_OK.match(n)]
    assert not unlisted, unlisted[:20]
    named = [n for n in scopes.values() if n]     # "" has no metadata
    share = sum(_block(n) is None for n in named) / len(named)
    assert share < 0.15, share


def test_op_scopes_is_not_a_recompilation(traced_step):
    step, _ = traced_step
    rec = obs.recompile_tracker().get(step._span_name)
    before = rec.stats()
    assert before["traces"] == 1
    assert xprof.op_scopes(step._span_name)
    after = rec.stats()
    assert after["traces"] == 1 and after["calls"] == before["calls"]
    assert _run(step, 1) and rec.traces == 1


def test_op_scopes_of_an_unknown_entry_point_is_none(metrics_on):
    assert xprof.op_scopes("TrainStep(Nothing)") is None


def test_parse_op_names_reads_plain_root_and_fusion_lines():
    text = "\n".join([
        'HloModule jit__step, entry_computation_layout={()->f32[]}',
        '  %fusion.12 = bf16[4,8]{1,0} fusion(%a), kind=kLoop, '
        'calls=%fused, metadata={op_name="jit(_step)/jvp(pt.ffn)/mul" '
        'source_file="x.py" source_line=3}',
        '  %flash_fwd.3 = bf16[4,8]{1,0} custom-call(%q), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(_step)/jvp(pt.attn)/flash_fwd/pallas_call"}, '
        'backend_config={"x":{}}',
        '  ROOT %tuple.1 = (f32[]) tuple(%b), metadata={op_name='
        '"jit(_step)/pt.optimizer/add"}',
        '  %copy.4 = f32[2]{0} copy(%c)',
    ])
    assert xprof.parse_op_names(text) == {
        "fusion.12": "jit(_step)/jvp(pt.ffn)/mul",
        "flash_fwd.3": "jit(_step)/jvp(pt.attn)/flash_fwd/pallas_call",
        "tuple.1": "jit(_step)/pt.optimizer/add",
        "copy.4": ""}


def test_what_the_compiler_added_takes_its_users_op_name():
    """A move between memory spaces carries no metadata: it is charged
    to the work that needed the data, through the done and the bitcast
    that join its pieces."""
    text = "\n".join([
        '  %slice-start.1 = ((bf16[8,8]), bf16[2,8]{1,0:S(1)}, s32[]) '
        'slice-start(%w), slice={[0:2], [0:8]}',
        '  %slice-done.1 = bf16[2,8]{1,0:S(1)} slice-done(%slice-start.1)',
        '  %custom-call.9 = bf16[8,8]{1,0:S(1)} custom-call('
        '%slice-done.1, %slice-done.2), '
        'custom_call_target="ConcatBitcast"',
        '  %fusion.7 = bf16[4,8]{1,0} fusion(%x, %custom-call.9), '
        'kind=kOutput, calls=%fc, metadata={op_name='
        '"jit(_step)/jvp(pt.attn)/dot_general"}',
        '  %copy-start.2 = (f32[8], f32[8], u32[]) copy-start(%fusion.7)',
        '  %copy-done.2 = f32[8]{0} copy-done(%copy-start.2)',
    ])
    got = xprof.parse_op_names(text)
    attn = "jit(_step)/jvp(pt.attn)/dot_general"
    assert got["slice-start.1"] == got["slice-done.1"] == attn
    assert got["custom-call.9"] == attn and got["fusion.7"] == attn
    # nothing uses the copy's result: it stays without a name
    assert got["copy-start.2"] == got["copy-done.2"] == ""


def test_a_kernel_the_compiler_renamed_is_charged_to_its_neighbours():
    """XLA:TPU rewrites ``lax.ragged_dot`` into Mosaic calls whose
    ``op_name`` is its own (``ragged-dot-none``, no ``/`` in it): the
    call is charged to the work that uses its result, and a result that
    leaves its computation to the work that made its newest operand."""
    experts = "jit(_step)/jvp(pt.moe_experts)/square"
    back = "jit(_step)/transpose(jvp(pt.moe_experts))/select_n"
    text = "\n".join([
        '  %fusion.1 = bf16[16,8]{1,0} fusion(%t), kind=kLoop, '
        'calls=%f1, metadata={op_name="jit(_step)/jvp(pt.moe_route)/'
        'gather"}',
        '  %ragged-dot-none.1 = bf16[16,4]{1,0} custom-call(%m, '
        '%fusion.1, %w), custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        '  %fusion.2 = bf16[16,4]{1,0} fusion(%ragged-dot-none.1), '
        'kind=kLoop, calls=%f2, metadata={op_name="' + experts + '"}',
        '  %fusion.3 = bf16[16,4]{1,0} fusion(%g), kind=kLoop, '
        'calls=%f3, metadata={op_name="' + back + '"}',
        '  %ragged-dot-none.2 = bf16[2,8,4]{2,1,0} custom-call(%m, '
        '%fusion.1, %fusion.3), custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        '  ROOT %tuple.5 = (bf16[2,8,4]) tuple(%ragged-dot-none.2)',
    ])
    got = xprof.parse_op_names(text)
    assert got["ragged-dot-none.1"] == experts
    assert got["ragged-dot-none.2"] == back


def test_host_spans_cover_the_whole_entry_point(metrics_on):
    step = _make_step("TrainStep")
    _run(step, 2)
    events = [e for e in obs.get_tracer().events()
              if e["name"].startswith("pt/")]
    assert [e["name"] for e in events] == SPANS * 2
    # nothing but the name, and the entry point on the dispatch
    assert [e.get("args") for e in events[:3]] == [
        None, {"fn": step._span_name}, None]
    step.run_steps(*(np.stack([a] * 2) for a in _batch()[:1]),
                   labels=tuple(np.stack([a] * 2) for a in _batch()[2:]),
                   masked_positions=np.stack([_batch()[1]] * 2))
    multi = [e for e in obs.get_tracer().events()
             if e["name"].startswith("pt/")][6:]
    assert [e["name"] for e in multi] == SPANS
    assert multi[1]["args"] == {"fn": step._span_name + ".multi"}


def test_sharded_entry_point_has_its_spans(metrics_on):
    step = _make_step("ShardedTrainStep")
    _run(step, 1)
    events = [e for e in obs.get_tracer().events()
              if e["name"].startswith("pt/")]
    assert [e["name"] for e in events] == SPANS[:2]
    assert events[1]["args"] == {"fn": step._span_name}


def test_metrics_off_keeps_nothing():
    assert not obs.enabled()
    obs.reset_all()
    step = _make_step("TrainStep")
    _run(step, 2)
    rec = obs.recompile_tracker().get(step._span_name)
    assert rec.traces == 1                      # no second trace
    assert rec._kept is None                    # no kept signature
    assert xprof.op_scopes(step._span_name) is None
    assert rec.traces == 1
    assert not [e for e in obs.get_tracer().events()
                if e["name"].startswith("pt/")]
    assert xprof.kernel_notes(step._span_name) == []


# -- kernels: a name on every call, the work beside it -------------------------

def _pallas_names(fn, *args):
    """The ``name`` of every pallas_call in the jaxpr of ``fn``."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def _flash_grad():
    from paddle_tpu.kernels import flash_attention as fa

    def run(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, interpret=True))

    def fn(q, k, v):
        return jax.grad(run, argnums=(0, 1, 2))(q, k, v)
    return fa, fn


def _kernel_wrappers():
    """(wrapper, arguments, names expected) for every kernel file."""
    from paddle_tpu.kernels import (fused_softmax_xent, grouped_matmul,
                                    layer_norm, paged_attention)
    f32 = jnp.float32
    x = jnp.ones((16, 128), f32)
    vec = jnp.ones((128,), f32)
    q = jnp.ones((1, 2, 16, 8), f32)
    _, flash = _flash_grad()
    pool = jnp.ones((4, 4, 2, 8), f32)
    tables = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.array([3, 5], jnp.int32)
    labels = jnp.zeros((16,), jnp.int32)

    def xent(h, w, b):
        return jax.grad(lambda h_, w_, b_: jnp.sum(
            fused_softmax_xent.fused_linear_softmax_xent(
                h_, w_, b_, labels, interpret=True)),
            argnums=(0, 1))(h, w, b)

    def grouped(a, w):
        return jax.grad(lambda a_, w_: jnp.sum(
            grouped_matmul.grouped_matmul(
                a_, w_, jnp.array([5, 11], jnp.int32),
                grouped_matmul.group_tiles(
                    jnp.array([5, 11], jnp.int32), 16, 8),
                interpret=True)), argnums=(0, 1))(a, w)

    return [
        (flash, (q, q, q), ["flash_fwd", "flash_bwd"]),
        (grouped, (x, jnp.ones((2, 128, 128), f32)),
         ["moe_gmm", "moe_gmm", "moe_tgmm"]),
        (lambda a, w, b: layer_norm.layer_norm_pallas(
            a, w, b, interpret=True), (x, vec, vec), ["layer_norm_fwd"]),
        (xent, (x, jnp.ones((256, 128), f32), jnp.zeros((256,), f32)),
         ["fused_xent_fwd", "fused_xent_bwd_dh", "fused_xent_bwd_dw"]),
        (lambda q1: paged_attention._paged_attention_impl(
            q1, pool, pool, tables, lens, interpret=True),
         (jnp.ones((2, 2, 8), f32),), ["paged_decode"]),
        (lambda q4: paged_attention._paged_attention_mq_impl(
            q4, jnp.array([2, 1], jnp.int32), pool, pool, tables, lens,
            interpret=True),
         (jnp.ones((2, 2, 2, 8), f32),), ["paged_ragged"]),
    ]


def test_every_pallas_call_carries_its_own_name(monkeypatch):
    seen = []
    for fn, args, want in _kernel_wrappers():
        got = _pallas_names(fn, *args)
        assert got == want, (got, want)
        seen += sorted(set(got))
    # the two-kernel backward: sequences longer than one block
    from paddle_tpu.kernels import flash_attention as fa
    monkeypatch.setattr(fa, "BLOCK_Q", 8)
    monkeypatch.setattr(fa, "BLOCK_K", 8)
    _, flash = _flash_grad()
    q = jnp.ones((1, 2, 16, 8), jnp.float32)
    got = _pallas_names(flash, q, q, q)
    assert got == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    seen += got[1:]
    assert len(seen) == len(set(seen)) == 12


def test_every_call_site_in_the_kernel_files_passes_a_name():
    root = os.path.join(os.path.dirname(pt.__file__), "kernels")
    names = []
    for f in sorted(os.listdir(root)):
        if not f.endswith(".py"):
            continue
        text = open(os.path.join(root, f)).read()
        calls = [m.start() for m in re.finditer(r"pl\.pallas_call\(",
                                                text)]
        for i, at in enumerate(calls):
            upto = calls[i + 1] if i + 1 < len(calls) else len(text)
            m = re.search(r'\bname="(\w+)"', text[at:upto])
            assert m, f"{f}: a pallas_call without name= at {at}"
            names.append(m.group(1))
    assert len(names) == 14 and len(set(names)) == 14, names


@pytest.mark.parametrize("b,h,t,d", [(2, 3, 16, 8), (1, 2, 32, 16)])
def test_noted_flash_work_is_the_hand_count(metrics_on, b, h, t, d):
    fa, flash = _flash_grad()
    q = jnp.ones((b, h, t, d), jnp.float32)
    jitted = obs.instrumented_jit(flash, "flash_probe")
    jax.block_until_ready(jitted(q, q, q))
    notes = xprof.kernel_notes("flash_probe")
    assert [n[0] for n in notes] == ["flash_fwd", "flash_bwd"]
    # forward: QK^T and PV, 2*t*t*d each a head; backward: five matmuls
    one = b * h * t * t * d
    assert notes[0][1] == 2 * 2 * one
    assert notes[1][1] == 5 * 2 * one
    # bytes: q, k, v (+ dO) read, the result(s) written, f32 rows
    assert notes[0][2] == 4 * b * h * t * d * 4 + b * h * t * 4
    assert notes[1][2] == 7 * b * h * t * d * 4 + 2 * b * h * t * 4
    assert fa.flash_fwd_work(b, h, t, t, d, 4, causal=True)[0] == 2 * one
    assert fa.flash_bwd_work(b, h, t, t, d, 4, matmuls=3)[0] == 6 * one
    assert fa.flash_bwd_work(b, h, t, t, d, 4, matmuls=4)[0] == 8 * one
    # lowering the entry point again must not count a site twice
    assert xprof.op_scopes("flash_probe")
    assert len(xprof.kernel_notes("flash_probe")) == 2


def test_kernel_notes_need_metrics_and_a_tracked_entry_point():
    assert not obs.enabled()
    _, flash = _flash_grad()
    q = jnp.ones((1, 2, 16, 8), jnp.float32)
    jax.block_until_ready(obs.instrumented_jit(flash, "flash_off")(
        q, q, q))
    assert xprof.kernel_notes("flash_off") == []
    xprof.note_kernel("stray", 1.0, 1.0)        # outside any trace
    assert xprof.kernel_notes("stray") == []


_CACHE_PROBE = r"""
import glob, os, sys
import jax, numpy as np
d = sys.argv[1]
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", d)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.models import (BertConfig, BertForPretraining,
                               pretraining_loss)
from paddle_tpu.static import TrainStep
# persistent-cache mode (probes ride the outputs), no harvest: the only
# second lowering is the one asked for below
pt.set_flags({"enable_metrics": True, "compile_cache_dir": d,
              "program_analytics": False})
step = TrainStep(BertForPretraining(BertConfig(
    vocab_size=128, hidden_size=32, num_hidden_layers=1,
    num_attention_heads=2, intermediate_size=64,
    max_position_embeddings=16)), pt.optimizer.AdamW(1e-3),
    pretraining_loss)
rng = np.random.default_rng(0)
ids = rng.integers(0, 128, (4, 16)).astype(np.int32)
pos = rng.integers(0, 16, (4, 3)).astype(np.int32)
mlm = rng.integers(0, 128, (4, 3)).astype(np.int32)
nsp = rng.integers(0, 2, (4,)).astype(np.int32)
for _ in range(2):
    float(step(ids, labels=(mlm, nsp), masked_positions=pos)["loss"])
steps = lambda: sorted(os.path.basename(f) for f in glob.glob(d + "/*")
                       if "jit__step" in f and "atime" not in f)
before = steps()
assert obs.xprof.op_scopes(step._span_name)
print("ENTRIES", len(before), len(steps()), before == steps())
"""


def test_op_scopes_loads_the_steps_own_cache_entry(tmp_path):
    """Whoever asks for the scope map on the chip must get a load from
    the persistent cache, not a second 80 s compile: the entry point
    lowered again has the cache key of the one that ran."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(pt.__file__))
    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE,
                           str(tmp_path)], env=env, text=True,
                          capture_output=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    said = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("ENTRIES")]
    assert said == ["ENTRIES 1 1 True"], proc.stdout[-2000:]
