"""The main path's Pallas kernels must compile for the chip they ship to.

Interpret mode cannot see what Mosaic refuses (the single-query decode
kernel passed every parity test and had never compiled for a TPU), so
each kernel of the train and serve paths is compiled here, at the width
the chip smoke runs it, for a DESCRIBED v5e — no chip attached, nothing
executed (/opt/skills/guides/on-chip-measurement/SKILL.md §2). A pass
says the compiler accepts the kernel; it says nothing about numbers or
time.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e 2x2; skip where the TPU compiler
    cannot describe it."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without the chip: the next run
    # would warn and recompile anyway
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # conftest pins "highest" for op-correctness tests; the chip runs
    # these kernels at its default precision, and Mosaic refuses an
    # fp32-precision matmul on bf16 operands
    with jax.default_matmul_precision("default"):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, chip, *shapes):
    """Compile ``fn`` for the described chip; return the program text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# GPT-2 small serving widths (chip_smoke serve phase): 12 heads of 64,
# block_size 16; BERT-base training widths: b16 x seq 512, hidden 768.
_H, _D, _BS, _POOL = 12, 64, 16, 512


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_attention_decode_compiles(chip, dtype):
    from paddle_tpu.kernels.paged_attention import _paged_attention_impl
    text = _compile(
        functools.partial(_paged_attention_impl, scale=_D ** -0.5), chip,
        ((8, _H, _D), dtype), ((_POOL, _BS, _H, _D), dtype),
        ((_POOL, _BS, _H, _D), dtype), ((8, 8), jnp.int32),
        ((8,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_attention_multiquery_compiles(chip):
    from paddle_tpu.kernels.paged_attention import (
        _paged_attention_mq_impl)
    text = _compile(
        functools.partial(_paged_attention_mq_impl, scale=_D ** -0.5),
        chip, ((8, 4, _H, _D), jnp.float32), ((8,), jnp.int32),
        ((_POOL, _BS, _H, _D), jnp.float32),
        ((_POOL, _BS, _H, _D), jnp.float32), ((8, 8), jnp.int32),
        ((8,), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_attention_bthd_fwd_bwd_compiles(chip):
    from paddle_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v, seed, kv_bias):
        out = flash_attention(q, k, v, seed=seed, dropout_p=0.1,
                              kv_bias=kv_bias, bthd=True)
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((16, 512, _H, _D), jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), chip, qkv, qkv,
                    qkv, ((1, 1), jnp.int32), ((16, 512), jnp.float32))
    assert "tpu_custom_call" in text


def test_flash_attention_causal_8192_positions_head_128_compiles(chip):
    """The hybrid language model's attention layer: 8192 positions of
    128-wide heads, causal. Every program keeps whole sequences in
    VMEM, and the dk/dv kernel's (q, dO and two float32 columns,
    double-buffered) take 25 MiB, past the compiler's 16 MiB scoped
    default: the call asks for what it needs (``_grid_params``)."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, bthd=True)
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((1, 8192, 4, 128), jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), chip, qkv, qkv,
                    qkv)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text


def test_flash_attention_under_the_block_diffusion_mask_compiles(chip):
    """The block-diffusion decoder's attention (sdar_30b_a3b_ep8_s8k):
    two sequences of 8192 tokens as 16,384 positions each, 32 heads of
    128, the mask computed in the kernels. The dk/dv kernel keeps q, dO
    and two float32 columns of 16,384 rows resident, double-buffered:
    48 MiB, inside the 100 it may ask for. No [2L, 2L] array exists."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    @jax.named_scope("pt.attn")     # as a model calls it: the Mosaic
    def loss(q, k, v):              # calls are named after the kernels
        out = flash_attention(q, k, v, bthd=True,
                              block_diffusion=(8192, 4))
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((2, 16384, 32, 128), jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), chip, qkv, qkv,
                    qkv)
    for kernel in ("bd_flash_fwd", "bd_flash_bwd_dq", "bd_flash_bwd_dkv"):
        assert len(_custom_calls(text, kernel)) == 1, kernel
    assert not _custom_calls(text, "flash_fwd")
    assert "16384,16384]" not in text


def test_a_block_diffusion_layers_gradient_runs_its_kernels_whole(
        chip, monkeypatch):
    """One layer of the block-diffusion decoder at the cell's widths
    (attention with q/k norm and rotary positions under the mask, then a
    softmax router over 16 held gated experts), recomputed in its
    backward pass as the model runs it (``nn.recompute_layer``), steered
    onto the kernels' side of the seams here (the process sees the CPU):
    the ``bd_flash`` kernels and the grouped matmuls of the gated
    experts (``w_gate`` and ``w_up`` one [16, 2048, 1536] operand)
    compile, the forward kernel runs once (its result is kept), and no
    [2L, 2L] array is in the optimized program."""
    import json
    import os

    from paddle_tpu import kernels, nn
    from paddle_tpu.models.sdar_moe import SdarMoeBlock, SdarMoeConfig
    from paddle_tpu.nn.layer import functional_call
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "sdar_30b_a3b.json")) as f:
        cfg = json.load(f)["model"]
    block = SdarMoeBlock(SdarMoeConfig(**cfg))
    block.to(dtype="bfloat16")
    block.train()
    params, buffers = block.param_dict(), block.buffer_dict()
    length = 8192
    pos = jnp.arange(2 * length, dtype=jnp.int32) % length

    def loss(p, x):
        def layer(h):
            y, stats = functional_call(block, p, buffers, h, pos)
            return y, stats["pairs_held"]
        y, held = nn.recompute_layer(layer)(x)
        return jnp.sum(y.astype(jnp.float32)), held

    abstract = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=chip), params)
    x = jax.ShapeDtypeStruct((2, 2 * length, cfg["hidden_size"]),
                             jnp.bfloat16, sharding=chip)
    compiled = jax.jit(jax.grad(loss, (0, 1), has_aux=True)).lower(
        abstract, x).compile()
    text = compiled.as_text()
    assert len(_custom_calls(text, "bd_flash_fwd")) == 1
    assert len(_custom_calls(text, "bd_flash_bwd_dq")) == 1
    assert len(_custom_calls(text, "bd_flash_bwd_dkv")) == 1
    assert _custom_calls(text, "moe_gmm") and _custom_calls(text, "moe_tgmm")
    assert "ragged-dot" not in text
    assert "16384,16384]" not in text
    # what one layer's backward pass keeps beside the state: under 4 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30


def test_grouped_matmul_kernels_of_held_pairs_compile(chip):
    """One window of the routed experts at the hybrid decoder's widths
    (12288 rows, 2688 x 1856, 8 held experts), forward and both
    gradients: the repo's ``moe_gmm`` and ``moe_tgmm`` (a group's whole
    matrix resident in VMEM, far past the scoped default: the calls ask
    for what they hold) and no grouped-matmul call of XLA's own. Every
    call does one pass over the true group sizes, not a dense product
    of every row with every expert."""
    from paddle_tpu.kernels import grouped_matmul as G
    rows, d, f, experts = 12288, 2688, 1856, 8

    def loss(x, w_in, w_out, sizes):
        tiles = G.group_tiles(sizes, rows)
        h = jnp.square(jax.nn.relu(
            G.grouped_matmul(x, w_in, sizes, tiles)))
        return jnp.sum(G.grouped_matmul(h, w_out, sizes, tiles)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*[
        jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in (
            ((rows, d), jnp.bfloat16), ((experts, d, f), jnp.bfloat16),
            ((experts, f, d), jnp.bfloat16), ((experts,), jnp.int32))
    ]).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    # the first product (the second's result is not wanted), the two
    # input gradients, the two weight gradients
    assert sum("moe_gmm" in c for c in calls) == 3
    assert sum("moe_tgmm" in c for c in calls) == 2
    one_pass = 2.0 * rows * d * f
    assert G.gmm_work(rows, d, f, experts, 2)[0] == one_pass
    assert 4.5 * one_pass < compiled.cost_analysis()["flops"] \
        < 8.5 * one_pass


# the hybrid decoder's Mamba-2 layer (nemotron3_nano_ep16_s8k): two
# sequences of 8192, 64 heads of 64 in 8 groups of state 128, chunk 128
_SSM = dict(rows=2, length=8192, heads=64, width=64, groups=8, state=128,
            chunk=128, hidden=2688)


def _custom_calls(text, name):
    return [line for line in text.splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line
            and f"%{name}" in line.split(" = ")[0]]


def test_ssd_scan_kernels_compile_at_the_hybrid_decoders_shapes(chip):
    """``ssd_fwd`` and ``ssd_bwd`` at the
    cell's shapes, bf16 with float32 dt: the transposes, the float32
    products with a triangle and the matmuls that contract a first
    dimension are what the interpreter cannot vouch for."""
    from paddle_tpu.kernels.ssd_scan import ssd_scan
    c = _SSM

    def loss(x, dt, b_mat, c_mat, a):
        return jnp.sum(ssd_scan(x, dt, b_mat, c_mat, a, c["chunk"])
                       .astype(jnp.float32))

    seq = (c["rows"], c["length"])
    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), chip,
        (seq + (c["heads"], c["width"]), jnp.bfloat16),
        (seq + (c["heads"],), jnp.float32),
        (seq + (c["groups"], c["state"]), jnp.bfloat16),
        (seq + (c["groups"], c["state"]), jnp.bfloat16),
        ((c["heads"],), jnp.float32))
    assert len(_custom_calls(text, "ssd_fwd")) == 1
    assert len(_custom_calls(text, "ssd_bwd")) == 1
    # the one residual besides the inputs: the state entering each chunk
    assert "f32[2,64,8,512,128]" in text


def test_a_mamba2_layers_gradient_runs_the_scan_kernels_and_no_loop(chip):
    """One ``Mamba2Mixer`` at the cell's widths under the layers'
    checkpoint, forward and backward, with the seam seeing a TPU: the
    scan is ``ssd_fwd`` twice (the forward pass and the recomputation)
    and ``ssd_bwd`` once, and under
    ``pt.ssm_scan`` there is no ``while`` (the map over sequences, the
    scan over chunk states) and no ``reduce-window`` (the cumulative sum
    of the log-decay as XLA lowers it: 32 ms a step, PERF.md section 6,
    PR 34)."""
    import re
    from unittest import mock

    import paddle_tpu as pt
    from paddle_tpu import kernels
    from paddle_tpu.nn.layer import functional_call
    c = _SSM
    mixer = pt.nn.Mamba2Mixer(c["hidden"], c["heads"], c["width"],
                              c["state"], c["groups"],
                              chunk_size=c["chunk"])
    mixer.to(dtype="bfloat16")
    params = mixer.param_dict()

    def loss(p, u):
        y = jax.checkpoint(lambda h: functional_call(mixer, p, {}, h))(u)
        # something after the layer reads its result, as the next layer
        # does: the forward pass's scan is no dead code
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    with mock.patch.object(kernels, "_on_tpu", lambda: True):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)
             for k, v in params.items()},
            jax.ShapeDtypeStruct((c["rows"], c["length"], c["hidden"]),
                                 jnp.bfloat16, sharding=chip)
        ).compile().as_text()
    assert len(_custom_calls(text, "ssd_fwd")) == 2
    assert len(_custom_calls(text, "ssd_bwd")) == 1
    scan = [line for line in text.splitlines()
            if re.search(r'op_name="[^"]*pt\.ssm_scan', line)]
    assert scan
    assert not [line for line in scan
                if re.search(r" (while|reduce-window)\(", line)]
    assert "reduce-window" not in text


def _float32_dots(text, scope):
    """``(operand_precision or None)`` of every dot and convolution
    traced under ``scope`` whose two operands are float32."""
    import re
    dtypes = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[", text,
                             re.MULTILINE))
    found = []
    for line in text.splitlines():
        ops = re.search(r" (?:convolution|dot)\(%([^,]+), %([^)]+)\)", line)
        if ops and re.search(rf'op_name="[^"]*{re.escape(scope)}', line) \
                and all(dtypes.get(o) == "f32" for o in ops.groups()):
            precision = re.search(r"operand_precision=\{([^}]*)\}", line)
            found.append(precision and precision.group(1))
    return found


def test_a_kda_layers_gradient_solves_its_chunks_by_products(chip):
    """One ``KimiDeltaAttention`` at the Kimi cell's widths (hidden 2304,
    32 heads of 128, chunks of 64 over 16,384 positions) under the
    layers' checkpoint, forward and backward: the chunks' unit-triangular
    solve is the block-product inverse, so there is no
    ``InvertDiagBlocksLowerTriangular`` custom call (86 us a chunk on a
    TPU v5e, PERF.md section 5) and no ``triangular-solve``, and every dot
    under ``pt.kda_scan`` whose operands are float32 runs at ``highest``
    (the chip's default is one bf16 pass)."""
    import paddle_tpu as pt
    from paddle_tpu.nn.layer import functional_call
    layer = pt.nn.KimiDeltaAttention(2304, 32, 128, chunk_size=64)
    layer.to(dtype="bfloat16")
    params = layer.param_dict()

    def loss(p, u):
        y = jax.checkpoint(lambda h: functional_call(layer, p, {}, h))(u)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)
         for k, v in params.items()},
        jax.ShapeDtypeStruct((1, 16384, 2304), jnp.bfloat16, sharding=chip)
    ).compile().as_text()
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert "triangular-solve" not in text
    dots = _float32_dots(text, "pt.kda_scan")
    # two levels of two products (blocks of 16 to 32 to 64) and the
    # product that applies the inverse, in the forward, the recomputed
    # forward and the backward
    assert len(dots) >= 3 * 5
    assert set(dots) == {"highest,highest"}


def test_layer_norm_fwd_bwd_compiles(chip):
    from paddle_tpu.kernels.layer_norm import layer_norm_pallas

    def loss(x, w, b):
        return jnp.sum(layer_norm_pallas(x, w, b, 1e-12)
                       .astype(jnp.float32))

    # value_and_grad: the backward is XLA ops, so the kernel is in the
    # program only while the forward value is an output
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), chip,
                    ((8192, 768), jnp.bfloat16), ((768,), jnp.float32),
                    ((768,), jnp.float32))
    assert "tpu_custom_call" in text


def test_fused_linear_softmax_xent_fwd_bwd_compiles(chip):
    from paddle_tpu.kernels.fused_softmax_xent import (
        fused_linear_softmax_xent)

    def loss(hidden, weight, bias, labels):
        return jnp.sum(fused_linear_softmax_xent(hidden, weight, bias,
                                                 labels))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), chip,
                    ((8192, 768), jnp.bfloat16),
                    ((30522, 768), jnp.bfloat16),
                    ((30522,), jnp.float32), ((8192,), jnp.int32))
    assert "tpu_custom_call" in text


@functools.lru_cache(maxsize=None)
def _encoder_layer_step_text(on_mesh: bool) -> str:
    """Compiled text of one BertEncoderLayer, forward and backward with
    its three dropouts at 0.1 under the generator the chip runs
    (``rbg``, core/random.py), for the described v5e: on one device at
    bert_base_s512's shape (b32 x s512, no mesh in scope), or over all
    four as dp2 x mp2 at bert_base_s512_dp2mp2's (b128: 64 sequences a
    chip, parameters placed by megatron_param_rule, the mesh in scope
    as ``ShardedTrainStep`` sets it)."""
    import contextlib
    from unittest import mock

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu import kernels
    from paddle_tpu.core import random as _random
    from paddle_tpu.models.bert import BertConfig, BertEncoderLayer
    from paddle_tpu.nn.layer import functional_call
    from paddle_tpu.parallel.spmd import megatron_param_rule
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    layer = BertEncoderLayer(BertConfig())
    layer.to(dtype="bfloat16")
    layer.train()
    params, buffers = layer.param_dict(), layer.buffer_dict()

    def loss(p, x, key):
        with _random.rng_scope(default=key, dropout=key):
            y = functional_call(layer, p, buffers, x)
        return jnp.sum(y.astype(jnp.float32))

    if on_mesh:
        mesh = Mesh(np.array(devices).reshape(2, 2), ("dp", "mp"))
        rule = megatron_param_rule()
        scope, batch = jax.sharding.set_mesh(mesh), 128

        def placed(name, a):
            spec = P("dp") if name == "x" else \
                rule(name, a) if name else P()
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=NamedSharding(mesh, spec))
    else:
        scope, batch = contextlib.nullcontext(), 32

        def placed(name, a):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=SingleDeviceSharding(devices[0]))

    was = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", "rbg")
    try:
        with mock.patch.object(kernels, "_on_tpu", lambda: True), scope:
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
                {k: placed(k, v) for k, v in params.items()},
                placed("x", jax.ShapeDtypeStruct((batch, 512, 768),
                                                 jnp.bfloat16)),
                placed(None, jax.eval_shape(lambda: jax.random.key(0)))
            ).compile().as_text()
    finally:
        jax.config.update("jax_default_prng_impl", was)


def _all_reduced(text):
    """(result type, op_name) of every all-reduce in a compiled text."""
    import re
    return [(m.group(1), m.group(2)) for m in re.finditer(
        r"^\s*(?:ROOT )?\S+ = (.*?) all-reduce(?:-start)?\(.*?"
        r"op_name=\"([^\"]*)\"", text, flags=re.M)]


def _assert_four_kernels(text):
    # flash forward and backward and the two norms' forward
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    for kernel in ("flash_fwd", "flash_bwd", "layer_norm_fwd"):
        assert kernel in text


def test_encoder_layer_step_holds_no_random_words(chip):
    """One BertEncoderLayer forward and backward at bert_base_s512's
    shape: the keep-masks are hashed inside the fusions that use them,
    so the only random bits the compiled program draws are the flash
    kernel's one seed, and it writes no word per element."""
    import math
    import re
    text = _encoder_layer_step_text(on_mesh=False)
    assert "flash_fwd" in text and "dropout_mask" in text
    drawn = re.findall(r"= (.*?) rng-bit-generator\(", text)
    assert len(drawn) == 1, drawn       # attention dropout's seed
    for dims in re.findall(r"\[([\d,]*)\]", drawn[0]):
        assert math.prod(int(d) for d in dims.split(",") if d) <= 4, drawn
    # what the program writes to memory are the results of the entry
    # computation's instructions (a fusion's inner values live in
    # registers): none is a word per activation element
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    results = re.findall(r"^\s*(?:ROOT )?\S+ = (.*?) [\w\-]+\(", entry,
                         flags=re.M)
    assert len(results) > 50
    wide = [r for r in results
            if re.search(r"[us]32\[32,512,(3072|768)\]", r)]
    assert not wide, wide


def test_encoder_layer_on_dp2mp2_exchanges_four_activations(chip):
    """Megatron's count: an activation crosses the mp link after the
    attention output projection and after FFN-out going forward, and
    for the input gradient of FFN-in and of q/k/v (once, for the sum of
    the three) going back. The parent read 8 here: a ``psum`` from
    transposing the norm kernel's shard_map at each norm, and the three
    q/k/v input gradients reduced one by one."""
    text = _encoder_layer_step_text(on_mesh=True)
    reduced = _all_reduced(text)
    # a tuple all-reduce counts once for each 50 MB element
    stream = [(kind, op) for kind, op in reduced
              for _ in range(kind.count("bf16[64,512,768]"))]
    assert len(stream) == 4, reduced
    assert not [op for _, op in reduced if "shard_map/psum" in op], reduced
    assert sorted(op.split("/")[1] for _, op in stream) == [
        "jvp(pt.attn)", "jvp(pt.ffn)", "transpose(jvp(pt.attn))",
        "transpose(jvp(pt.ffn))"], stream
    _assert_four_kernels(text)      # each on its shard still


def test_encoder_layer_on_one_chip_has_its_kernels_and_no_exchange(chip):
    """With no mesh in scope nothing of the mesh path shows: the
    kernels are called directly and nothing is reduced."""
    text = _encoder_layer_step_text(on_mesh=False)
    assert "all-reduce" not in text
    _assert_four_kernels(text)


# The accepted cells' flash kernels (BERT's one tile with dropout, the
# hybrid decoder's causal 8192 positions, the block-diffusion decoder's
# 16,384), as the PR 38 tree lowered them for a v5e: the sha256 of each
# Mosaic module's text without source locations. The value width must
# leave them as they were when v is as wide as q; a change to these
# kernels changes these digests, and the accepted cells' programs with
# them.
_ACCEPTED = {
    "bert": {
        "flash_fwd": "ea2d6094799bea0d6e6973dbddd94ce0"
                     "3c8000c359145d1d43adb0e3cbcda621",
        "flash_bwd": "2bf583091f06fabc59b787ab884cb204"
                     "f6c03baca442c82bfb3aaf9555106d76"},
    "causal": {
        "flash_fwd": "fce1d5fd03b5e101b36e8a82c9fd96d9"
                     "54451060cdc89bdaa68f70f575a7a665",
        "flash_bwd_dq": "a652d6432ea0663e3e4ccdcdeeeb546b"
                        "b1374a13309a318d31d350b9b3dbb48b",
        "flash_bwd_dkv": "498d384d3d8b2cf7e7295b2612b0a84e"
                         "42ae5e0ddcd23235b158629f4178e81d"},
    "bd": {
        "bd_flash_fwd": "76c25fcc52f20c7a8f9e368c1ad2132d"
                        "08e1a0af4e5f1bc85b3a5f4b5fb3ad11",
        "bd_flash_bwd_dq": "a6e2bec7a413fbc78a625ed1d8c3c3af"
                           "5bab4dba117a76e24dd6b0ed3064b5d7",
        "bd_flash_bwd_dkv": "6fcd53f8efbc3ebe348fbc9fffe611e4"
                            "fdafd25994dd98dc999575edcfde99fe"},
}


def _mosaic_texts(fn, chip, *shapes):
    """{kernel name: its Mosaic module's text, source locations left
    out} of ``fn`` lowered for ``chip``."""
    import base64
    import re

    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu as tpu_dialect
    from jax._src.lib.mlir import ir

    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    with jax.default_matmul_precision("default"):
        text = jax.jit(fn).lower(*args).as_text()
    out = {}
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text):
        with jmlir.make_ir_context() as ctx:
            tpu_dialect.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(body))
            asm = module.operation.get_asm(enable_debug_info=False)
        out[re.match(r"module @(\w+)", asm).group(1)] = asm
    return out


def _flash_grad(**kw):
    from paddle_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, bthd=True, **kw)
                       .astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("cell", sorted(_ACCEPTED))
def test_accepted_cells_flash_kernels_are_unchanged(chip, cell):
    """The value width (``d_v``) leaves the kernels of the accepted cells,
    where v is as wide as q and k, as they were: their lowered text is
    the PR 38 tree's."""
    import hashlib

    bf = jnp.bfloat16
    fn, shape = {
        "bert": (_flash_grad(dropout_p=0.1, seed=jnp.ones((1, 1), jnp.int32)),
                 (32, 512, 12, 64)),
        "causal": (_flash_grad(causal=True), (2, 8192, 32, 128)),
        "bd": (_flash_grad(block_diffusion=(8192, 4)), (2, 16384, 32, 128)),
    }[cell]
    texts = _mosaic_texts(fn, chip, *[(shape, bf)] * 3)
    got = {k: hashlib.sha256(t.encode()).hexdigest()
           for k, t in texts.items()}
    assert got == _ACCEPTED[cell]


def test_value_width_flash_kernels_take_256_value_lanes(chip):
    """At the latent attention's widths a program holds a pair of heads:
    384 lanes of q and k, 256 of v, the output and dv."""
    bf = jnp.bfloat16
    texts = _mosaic_texts(_flash_grad(causal=True), chip,
                          ((1, 2048, 4, 192), bf), ((1, 2048, 4, 192), bf),
                          ((1, 2048, 4, 128), bf))
    assert set(texts) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    fwd = texts["flash_fwd"].split("\n")[2]         # the arguments
    assert "memref<1x512x384xbf16" in fwd
    assert "memref<1x2048x256xbf16" in fwd and "memref<1x512x256xbf16" in fwd


def test_latent_attention_flash_kernels_compile_at_the_cells_shape(chip):
    """The latent attention layer of the Kimi Linear cell
    (kimi_linear_48b_a3b_ep32_s16k): one sequence of 16,384 positions,
    32 heads, q and k 192 wide, v 128, causal. A program holds a pair of
    heads, whole sequences of k and v (or q and dO) resident: Mosaic
    takes the value width's blocks and the VMEM they ask for."""
    qk = ((1, 16384, 32, 192), jnp.bfloat16)
    text = _compile(_flash_grad(causal=True), chip, qk, qk,
                    ((1, 16384, 32, 128), jnp.bfloat16))
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text
    assert "16384,16384]" not in text


# The kernels' other walks (a tail, fewer queries than keys, one tile,
# blocks that straddle tiles) as the tree before the window lowered them,
# digested as `_ACCEPTED` is: the sliding window leaves every kernel
# without it as it was.
_UNWINDOWED = {
    "plain_tail": {
        "flash_fwd": "1e0d9bd0e17238501b7f98453ddc990c"
                     "0ca88e8ffb8f6cc333e3ba3c39ee3104",
        "flash_bwd_dq": "88cb37023ea349e5244cb26202d342c1"
                        "35e95288a8f1f3296f91a321b68fdb48",
        "flash_bwd_dkv": "7065cc51331274105de1d6a217bc4999"
                         "4b5fe34956ebdbbba83e6a8edf5dcc8f"},
    "causal_offset": {
        "flash_fwd": "f1fce6723dd8f19aa8fa0b3f46c4831f"
                     "33714dc0a9707fc73c7ab7030a1237a6",
        "flash_bwd_dq": "3bfb09478319d6735f5e1e4a0c311053"
                        "b7974ae7da030c5fa20196eaab04dc7a",
        "flash_bwd_dkv": "3f02d92a8634673360215e1d24af66f8"
                         "4a277e5632f14cfa52cb70f9ecbf1154"},
    "causal_tail": {
        "flash_fwd": "ea00970123ca934eafd09085555a0907"
                     "89693027af42a5c1b909dbe9f0c0d8d2",
        "flash_bwd_dq": "de8b6625778d1522e2cf9e37f0a62ce1"
                        "81375c29da0e15a58c79d5b59f3ccd5a",
        "flash_bwd_dkv": "282af9581cfa830c0baaa6ac2f54306f"
                         "c6720ac5e067abdcf96e82e4f22636ae"},
    "bd_unaligned": {
        "bd_flash_fwd": "8e9d31c5ae9d82340e3f159b9ccee613"
                        "d63437b74812f4bcc3b4030b9f4f46d2",
        "bd_flash_bwd_dq": "1f3d032d7ee4f490e8a9ad8fd4619b32"
                           "19bdda773ffa457a3740c05151fd4016",
        "bd_flash_bwd_dkv": "708c8219f73a9b1a9a32bbf70ab316b0"
                            "afb79d3a94505e2e6840e939e91e0f62"},
    "causal_one_tile": {
        "flash_fwd": "ca65400a57d430644cf13963ca40baa5"
                     "11cc4c8c038f5927d678ccb58f8c2de5",
        "flash_bwd": "d1dd29cf75646b692961276c2aacd9c6"
                     "f6ca993b3dd43ad860025eca427b91dd"},
}


@pytest.mark.parametrize("case", sorted(_UNWINDOWED))
def test_kernels_without_a_window_are_unchanged(chip, case):
    import hashlib

    bf = jnp.bfloat16
    fn, shapes = {
        "plain_tail": (_flash_grad(), [(1, 1000, 4, 128)] * 3),
        "causal_offset": (_flash_grad(causal=True), [
            (1, 1024, 4, 128), (1, 2048, 4, 128), (1, 2048, 4, 128)]),
        "causal_tail": (_flash_grad(causal=True), [(1, 1800, 4, 128)] * 3),
        "bd_unaligned": (_flash_grad(block_diffusion=(1000, 8)),
                         [(1, 2000, 4, 128)] * 3),
        "causal_one_tile": (_flash_grad(causal=True),
                            [(2, 384, 4, 128)] * 3),
    }[case]
    texts = _mosaic_texts(fn, chip, *[(s, bf) for s in shapes])
    got = {k: hashlib.sha256(t.encode()).hexdigest()
           for k, t in texts.items()}
    assert got == _UNWINDOWED[case]


def test_window_kernels_compile_at_the_cells_shape(chip):
    """The SmallThinker cell's window layers (smallthinker_21b_a3b_ep4_
    s16k): one sequence of 16,384 positions, 28 heads of 128, windows of
    4096 keys: the ``swa_flash`` kernels, one call each, and no [L, L]
    array."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    @jax.named_scope("pt.attn")     # as a model calls it: the Mosaic
    def loss(q, k, v):              # calls are named after the kernels
        out = flash_attention(q, k, v, causal=True, bthd=True, window=4096)
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((1, 16384, 28, 128), jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), chip, qkv, qkv,
                    qkv)
    for kernel in ("swa_flash_fwd", "swa_flash_bwd_dq", "swa_flash_bwd_dkv"):
        assert len(_custom_calls(text, kernel)) == 1, kernel
    assert not _custom_calls(text, "flash_fwd")
    assert "16384,16384]" not in text


def test_a_window_layers_gradient_runs_its_kernels_once(chip, monkeypatch):
    """One window layer of the SmallThinker cell at its widths (rotary
    positions, the window of 4096, a softmax router that reads the
    layer's input before attention over 16 held ReGLU experts),
    recomputed in its backward pass as the model runs it, steered onto
    the kernels' side of the seams: the ``swa_flash`` kernels and the
    grouped matmuls compile, the forward kernel runs once (its result is
    kept), and no [L, L] array is in the optimized program."""
    import json

    from paddle_tpu import kernels, nn
    from paddle_tpu.models.smallthinker import (SmallThinkerBlock,
                                                SmallThinkerConfig)
    from paddle_tpu.nn.layer import functional_call
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        cfg = json.load(f)["model"]
    block = SmallThinkerBlock(SmallThinkerConfig(**cfg), 1)
    block.to(dtype="bfloat16")
    block.train()
    params, buffers = block.param_dict(), block.buffer_dict()

    def loss(p, x):
        def layer(h):
            y, stats = functional_call(block, p, buffers, h)
            return y, stats["pairs_held"]
        y, held = nn.recompute_layer(layer)(x)
        return jnp.sum(y.astype(jnp.float32)), held

    abstract = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=chip), params)
    x = jax.ShapeDtypeStruct((1, 16384, cfg["hidden_size"]), jnp.bfloat16,
                             sharding=chip)
    compiled = jax.jit(jax.grad(loss, (0, 1), has_aux=True)).lower(
        abstract, x).compile()
    text = compiled.as_text()
    for kernel in ("swa_flash_fwd", "swa_flash_bwd_dq", "swa_flash_bwd_dkv"):
        assert len(_custom_calls(text, kernel)) == 1, kernel
    assert _custom_calls(text, "moe_gmm") and _custom_calls(text, "moe_tgmm")
    assert "ragged-dot" not in text
    assert "16384,16384]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30
