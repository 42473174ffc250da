"""Pallas custom kernels for hot ops.

TPU-native replacement for the reference's hand-written CUDA kernels
(/root/reference/paddle/fluid/operators/fused/: multihead_matmul_op.cu,
fused_fc_elementwise_layernorm_op.cu; operators/math/bert_encoder_functor.cu).
Routing policy: each ``maybe_*`` entry point
checks the ``use_pallas_kernels`` flag and the backend, and falls back to the
pure-XLA composition in ops/ — so CPU tests and TPU production share one
call site. Kernels themselves live in sibling modules (flash_attention,
layer_norm, fused_softmax_xent, paged_attention, grouped_matmul,
ssd_scan).
"""

from __future__ import annotations

from typing import Optional

import jax

from ..flags import GLOBAL_FLAGS


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_enabled() -> bool:
    return GLOBAL_FLAGS.get("use_pallas_kernels") and _on_tpu()


def _per_shard(kernel, operands, dims, out_dims):
    """Call ``kernel(*operands, shard)`` once per shard of the mesh in
    scope, or directly when there is none.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so under a multi-device mesh — the one
    ``ShardedTrainStep`` sets while it traces — each routed kernel runs
    on the local block of its operands. ``dims`` gives, per operand, a
    ``{dimension: mesh axis}`` dict (``out_dims`` the same for the
    result, which has operand 0's rank) in the standard axis names of
    parallel/mesh.py: batch over
    ``dp``, heads over ``mp``. An axis is used only where the mesh has
    it with size > 1 and it divides that dimension of every operand
    that names it (else that dimension is whole on every device); any
    other layout GSPMD holds is resharded to this one around the call.
    ``shard`` is a traced int32 that differs between shards (0 without
    a mesh), for kernels that draw random bits.

    Never differentiate through this call when an operand is whole over
    a mesh axis (layer norm's rows over ``mp``): transposing the
    ``shard_map`` sums that operand's cotangent over the axis, an
    all-reduce of identical copies. Such a kernel keeps its
    ``custom_vjp`` outside (kernels/layer_norm.py); flash attention
    names both axes on q/k/v and has nothing to sum.
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import auto_axis_sizes
    sizes = auto_axis_sizes()
    if not sizes:
        return kernel(*operands, jnp.int32(0))
    # absent optional operands (None) stay outside the shard_map
    live = [i for i, x in enumerate(operands) if x is not None]
    used = {ax for i in live for ax in dims[i].values() if ax in sizes}
    used = {ax for ax in used
            if all(operands[i].shape[dim] % sizes[ax] == 0
                   for i in live for dim, a in dims[i].items()
                   if a == ax)}

    def spec(ndim, d):
        return P(*(d.get(i) if d.get(i) in used else None
                   for i in range(ndim)))

    def local(*blocks):
        shard = jnp.int32(0)
        for ax in sorted(used):
            shard = shard * sizes[ax] + jax.lax.axis_index(ax)
        args = [None] * len(operands)
        for i, blk in zip(live, blocks):
            args[i] = blk
        return kernel(*args, shard)

    from ..parallel._shard_map import shard_map
    return shard_map(
        local, jax.sharding.get_abstract_mesh(),
        in_specs=tuple(spec(operands[i].ndim, dims[i]) for i in live),
        out_specs=spec(operands[0].ndim, out_dims),
        check_vma=False)(*(operands[i] for i in live))


# Memory bound for routing NARROW head dims (d%8, not d%128) to flash
# in EVAL mode: at 8k+ the [T, T] fwd scores alone are HBM-scale. A
# fixed constant, not the flash_attention_min_seq flag — that flag may
# be lowered from a measured d=128 table, which is no evidence about
# narrow-head eval.
_NARROW_HEAD_EVAL_MIN_SEQ = 8192


def maybe_layer_norm(x, weight, bias, epsilon: float, begin_norm_axis: int):
    from ..ops.nn_functional import layer_norm as ref_impl
    if pallas_enabled() and GLOBAL_FLAGS.get("use_pallas_layer_norm") \
            and begin_norm_axis == x.ndim - 1 and x.ndim >= 2:
        try:
            from .layer_norm import layer_norm_pallas
            return layer_norm_pallas(x, weight, bias, epsilon)
        # ptlint: disable=silent-failure -- NotImplementedError is the kernel's documented "shape unsupported" signal; the reference impl below is the answer
        except NotImplementedError:
            pass
    return ref_impl(x, weight, bias, epsilon, begin_norm_axis)


def maybe_group_tiles(sizes, rows: int):
    """The walk over (group, row tile) pairs that the grouped-matmul
    kernels of one window share (kernels/grouped_matmul.py), or ``None``
    where ``maybe_grouped_matmul`` runs ``jax.lax.ragged_dot``: off a
    TPU, under a mesh (GSPMD cannot partition a Mosaic kernel, and the
    held experts are one rank's), or for rows the tile does not
    divide."""
    from .grouped_matmul import ROW_TILE, group_tiles
    if not pallas_enabled() or rows % ROW_TILE:
        return None
    from ..parallel.mesh import auto_axis_sizes
    if auto_axis_sizes():
        return None
    return group_tiles(sizes, rows)


def maybe_grouped_matmul(lhs, rhs, sizes, tiles=None):
    """Rows of group ``i`` of ``lhs`` [m, k] times ``rhs[i]`` of [g, k,
    n], for ``sizes`` [g] rows a group: the repo's kernels, forward and
    both gradients, where ``tiles`` is ``maybe_group_tiles``'s walk, and
    ``jax.lax.ragged_dot`` where it is ``None``."""
    if tiles is None:
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    from .grouped_matmul import grouped_matmul
    return grouped_matmul(lhs, rhs, sizes, tiles)


def maybe_ssd_scan(x, dt, b_mat, c_mat, a, chunk: int):
    """``nn.layers.ssm.ssd_chunked_scan`` by the fused kernels of
    kernels/ssd_scan.py, forward and gradient, or ``None`` where the
    caller runs the XLA form: off a TPU, under a mesh (GSPMD cannot
    partition a Mosaic kernel), for a length the chunk does not divide,
    for a chunk or a state that are no whole lane tiles, or for heads
    that are no whole sublane tiles. A site that takes the kernels notes
    itself (``pt_ssd_scan_kernel_sites``)."""
    from .ssd_scan import ssd_scan, supported
    if not pallas_enabled() or not supported(x.shape, b_mat.shape, chunk):
        return None
    from ..parallel.mesh import auto_axis_sizes
    if auto_axis_sizes():
        return None
    from ..observability.xprof import note_ssd_scan_kernel
    note_ssd_scan_kernel()
    return ssd_scan(x, dt, b_mat, c_mat, a, chunk)


def fused_softmax_xent_enabled() -> bool:
    return pallas_enabled() and GLOBAL_FLAGS.get("fused_softmax_xent")


def maybe_fused_linear_xent(hidden, weight, bias, labels,
                            ignore_index: int = -100):
    """Per-position softmax cross-entropy of the linear projection
    ``logits = hidden @ weight.T + bias`` — the masked-LM loss region.
    hidden: [..., H]; weight: [V, H]; bias: [V] or None; labels: [...]
    int. Returns f32 loss of labels' shape (0.0 at ignore_index).

    Routed (FLAGS_fused_softmax_xent + Pallas on-accelerator) the
    [..., V] logits tensor is never materialized in either direction;
    the fallback composes the projection with the reference
    ops.loss.softmax_with_cross_entropy so both paths share semantics.
    """
    if fused_softmax_xent_enabled():
        from .fused_softmax_xent import fused_linear_softmax_xent
        return fused_linear_softmax_xent(hidden, weight, bias, labels,
                                         ignore_index=ignore_index)
    import jax.numpy as jnp

    from ..ops.loss import softmax_with_cross_entropy
    logits = hidden @ weight.T
    if bias is not None:
        logits = logits + bias
    loss = softmax_with_cross_entropy(
        logits, labels[..., None], ignore_index=ignore_index)
    return jnp.squeeze(loss, axis=-1)


def maybe_paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                          scale: Optional[float] = None):
    """Ragged paged decode attention over the serving KV block pool
    (q [B, H, D], pools [N, block_size, H, D] — see
    kernels/paged_attention.py). Unlike the other maybe_* entries this
    has no separate XLA composition and no flag: on a TPU the kernel
    compiles or the call raises; on any other backend the SAME kernel
    runs under the Pallas interpreter, so tier-1 exercises the exact
    production code path (the dense gather reference exists for parity
    tests, not routing)."""
    from .paged_attention import paged_attention
    return paged_attention(q, k_pool, v_pool, block_tables,
                           context_lens, scale=scale,
                           interpret=not _on_tpu())


def maybe_paged_attention_multiquery(q, q_lens, k_pool, v_pool,
                                     block_tables, context_lens,
                                     scale: Optional[float] = None):
    """Ragged MULTI-QUERY paged attention — the speculative-decode
    verify step (q [B, Qmax, H, D] plus per-sequence q_lens; see
    kernels/paged_attention.py). Same routing story as
    maybe_paged_attention: no separate XLA composition — interpreted
    only off-TPU — and a Qmax == 1 batch reduces to the single-query
    kernel path bit-for-bit."""
    from .paged_attention import paged_attention_multiquery
    return paged_attention_multiquery(q, q_lens, k_pool, v_pool,
                                      block_tables, context_lens,
                                      scale=scale,
                                      interpret=not _on_tpu())


def _is_key_padding_mask(mask, batch: int, tk: int) -> bool:
    """True for exactly-shaped [B, 1, 1, Tk] masks (no broadcasting)."""
    return (getattr(mask, "ndim", 0) == 4
            and mask.shape[0] == batch
            and mask.shape[1] == 1 and mask.shape[2] == 1
            and mask.shape[3] == tk)


def _mask_to_kv_bias(mask):
    """[B, 1, 1, Tk] mask -> [B, Tk] additive f32 bias for the flash
    kernel. Bool masks are KEEP masks (True = attend); float masks are
    already additive. Pure helper so the polarity/slicing is testable
    off-TPU."""
    import jax.numpy as jnp

    from .flash_attention import _NEG_INF
    if mask.dtype == jnp.bool_:
        return jnp.where(mask[:, 0, 0, :], 0.0, jnp.float32(_NEG_INF))
    return mask[:, 0, 0, :].astype(jnp.float32)


def _block_diffusion_attention(q, k, v, scale, layout: str, bd):
    """``maybe_flash_attention`` under the block-diffusion mask."""
    import jax.numpy as jnp

    from .flash_attention import bd_allowed, bthd_supported, flash_attention
    bthd = layout == "bthd"
    t = q.shape[1 if bthd else 2]
    d = q.shape[-1]
    from ..parallel.mesh import auto_axis_sizes
    if (pallas_enabled() and d % 128 == 0 and not auto_axis_sizes()
            and (not bthd or bthd_supported(d, q.shape[2]))):
        from ..observability.xprof import note_bd_attention
        note_bd_attention()
        return flash_attention(q, k, v, scale=scale, bthd=bthd,
                               block_diffusion=tuple(bd))
    pos = jnp.arange(t, dtype=jnp.int32)
    mask = bd_allowed(pos[:, None], pos[None, :], t, *bd)
    from ..ops.attention import scaled_dot_product_attention as ref_impl
    if bthd:
        out = ref_impl(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                       jnp.moveaxis(v, 2, 1), mask=mask, scale=scale)
        return jnp.moveaxis(out, 1, 2)
    return ref_impl(q, k, v, mask=mask, scale=scale)


def _window_mask(mask, tq: int, tk: int, window: int):
    """``mask`` (none, a keep mask or an additive one) with the keys
    outside the sliding window of ``window`` keys hidden, bottom-right
    aligned as the causal mask is."""
    import jax.numpy as jnp
    pos = jnp.arange(tq)[:, None] + (tk - tq)
    inside = jnp.arange(tk)[None, :] > pos - window
    if mask is None:
        return inside
    if mask.dtype == jnp.bool_:
        return jnp.logical_and(mask, inside)
    return jnp.where(inside, mask, jnp.finfo(mask.dtype).min)


def maybe_flash_attention(q, k, v, mask=None, scale: Optional[float] = None,
                          causal: bool = False, dropout_p: float = 0.0,
                          training: bool = False, layout: str = "bhtd",
                          block_diffusion=None,
                          window: Optional[int] = None):
    """q/k/v: [B, H, T, D] (``layout="bhtd"``, default) or
    [B, T, H, D] (``layout="bthd"`` — the projections' natural layout;
    the flash kernel gathers heads inside its block DMA, so the routed
    path runs ZERO physical transposes, measured ~2.2 ms/step of
    transpose_jvp in the r5 BERT b8 profile. The output layout matches
    the input layout; the XLA fallback transposes to/from BHTD
    internally, costing exactly what the caller-side split used to).

    Routing: attention goes to the Pallas flash kernel only at
    key-sequence lengths >= the mode's gate: flash_attention_min_seq
    (eval; memory-motivated — beyond it XLA's [T, T] scores are
    HBM-scale by arithmetic) or flash_attention_min_seq_train
    (measured: the r5 in-model bert_b8_flash512 A/B won at seq 512).
    Paths where O(T) memory is the whole point (ring/Ulysses long
    context) route to the kernel directly, not through this gate.
    Attention dropout runs INSIDE the kernel (counter-based mask, same
    bits in the recompute backward), so training models like BERT
    (head dim 64, attn dropout 0.1) stay on the flash path when
    routed.

    ``block_diffusion=(length, block)`` (static ints) is the
    block-diffusion training mask over ``2 * length`` positions in
    ``causal``'s place (``flash_attention`` has the rule): on a TPU the
    ``bd_flash_*`` kernels whatever the length, with no [2L, 2L] array
    anywhere; elsewhere plain XLA attention under the dense mask, for
    small sizes only. A site that takes the kernels notes itself
    (``pt_bd_attention_sites``).

    ``window`` (a static int, with ``causal``) hides every key but the
    ``window`` last up to a query's own: routed, the ``swa_flash_*``
    kernels, which visit only the tiles inside the window (a site that
    takes them notes itself, ``pt_swa_attention_sites``); elsewhere XLA
    attention under the dense mask. A window that holds every key is
    the causal mask.
    """
    from ..ops.attention import scaled_dot_product_attention as ref_impl
    import jax.numpy as jnp

    if block_diffusion is not None:
        return _block_diffusion_attention(q, k, v, scale, layout,
                                          block_diffusion)

    bthd = layout == "bthd"
    t_axis = 1 if bthd else 2
    d = q.shape[-1]
    if window is not None:
        from .flash_attention import _window_of
        if not causal:
            raise ValueError(f"window={window} is a causal mask's: "
                             "causal=True with it")
        window = _window_of(window, k.shape[t_axis])
    # d%128 keeps MXU lanes full. Narrower head dims (BERT's 64) route
    # only where flash's O(T) memory is the point: training (the XLA
    # backward materializes [T,T] probs in fp32) or eval at lengths
    # where the fwd scores alone are HBM-scale. The eval floor below is
    # deliberately NOT the flash_attention_min_seq flag: lowering that
    # flag from a measured d=128 `flash` table says nothing about
    # narrow-head eval (no capture stage measures it), so the memory
    # bound stays fixed.
    tk = k.shape[t_axis]
    d_ok = d % 128 == 0 or (d % 8 == 0 and (
        training or tk >= _NARROW_HEAD_EVAL_MIN_SEQ))
    # key-padding masks [B, 1, 1, Tk] (the exact shape BertModel/
    # variable-length batches produce) run INSIDE the kernel as an
    # additive key bias; broadcastable or richer mask shapes fall back
    # to the XLA path. Conversion happens only on the routed branch.
    mask_ok = mask is None or _is_key_padding_mask(mask, q.shape[0], tk)
    min_seq = GLOBAL_FLAGS.get("flash_attention_min_seq")
    if training:
        # the train crossover is its own measured number (XLA's
        # backward re-materializes [T, T] probs in fp32); 0 = shared
        min_seq = GLOBAL_FLAGS.get("flash_attention_min_seq_train") \
            or min_seq
    if (pallas_enabled() and mask_ok and q.ndim == 4 and d_ok
            and tk >= min_seq):
        from ..parallel.mesh import DP, MP
        from .flash_attention import bthd_supported, flash_attention
        kv_bias = None if mask is None else _mask_to_kv_bias(mask)
        seed = None
        drop = float(dropout_p) if training else 0.0
        if drop > 0.0:
            from ..core import random as _random
            seed = jax.random.randint(
                _random.next_key("dropout"), (1, 1), 0, 2 ** 31 - 1,
                dtype=jnp.int32)

        def kernel(q, k, v, seed, kv_bias, shard):
            if seed is not None:
                # the keep mask hashes (seed, LOCAL head, position):
                # give each shard its own stream (int32 wrap is fine)
                seed = seed + shard * jnp.int32(0x3C6EF35F)
            kw = dict(seed=seed, causal=causal, scale=scale,
                      dropout_p=drop, kv_bias=kv_bias, window=window)
            if bthd and not bthd_supported(d, q.shape[2], v.shape[-1]):
                # geometry the BTHD block tiling can't express (e.g.
                # d=32, odd local head count): still flash, via the
                # transpose layout
                out = flash_attention(
                    jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                    jnp.moveaxis(v, 2, 1), **kw)
                return jnp.moveaxis(out, 1, 2)
            return flash_attention(q, k, v, bthd=bthd, **kw)

        qkv = {0: DP, 2 if bthd else 1: MP}   # batch, heads
        if window is not None:
            from ..observability.xprof import note_swa_attention
            note_swa_attention()
        return _per_shard(kernel, (q, k, v, seed, kv_bias),
                          (qkv, qkv, qkv, {}, {0: DP}), qkv)
    if window is not None:
        mask = _window_mask(mask, q.shape[t_axis], tk, window)
    if bthd:
        # XLA fallback wants [B, H, T, D]; the transpose pair here
        # costs what the caller-side head split used to cost
        out = ref_impl(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                       jnp.moveaxis(v, 2, 1), mask=mask, scale=scale,
                       causal=causal, dropout_p=dropout_p,
                       training=training)
        return jnp.moveaxis(out, 1, 2)
    return ref_impl(q, k, v, mask=mask, scale=scale, causal=causal,
                    dropout_p=dropout_p, training=training)
