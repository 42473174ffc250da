"""What a recomputed layer keeps.

``jax.checkpoint`` round a layer keeps the layer's input and makes
everything else again in the backward pass. Two kinds of result are dear
to make again and cheap to hold, and :func:`recompute_layer` keeps them,
by the names their makers give them (``jax.ad_checkpoint.
checkpoint_name``; a name outside a policy is the identity):

- ``flash_out``, ``flash_lse``: the flash kernel's output, as the
  kernel wrote it, and its row statistics float32 [B, H, T]
  (``kernels.flash_attention``). The backward kernels read both, and a
  recomputation that needs either runs the whole forward kernel.
- ``moe_chosen``, ``moe_order``, ``moe_load``: an expert layer's routing
  plan (``nn.DroplessMoE``): the chosen experts int32 [N, k], the pairs
  sorted by expert int32 [N k] and the pairs an expert got int32 [E].
  Integers, so no gradient needs them made again; the router's matmul,
  the scores and the weights are recomputed, reading the kept choice.

A layer that holds neither kind (a Mamba-2 layer) keeps nothing more
than its input.
"""

from __future__ import annotations

from typing import Callable

import jax

from ...observability import xprof

__all__ = ["KEPT_NAMES", "recompute_layer"]

KEPT_NAMES = ("flash_out", "flash_lse", "moe_chosen", "moe_order",
              "moe_load")

_named = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)


def _kept(prim, *avals, **params) -> bool:
    """The policy: JAX asks it once for every equation of the layer
    whose inputs the forward pass knows, and a kept result notes its
    bytes (``pt_remat_kept_bytes``)."""
    kept = _named(prim, *avals, **params)
    if kept:
        xprof.note_remat_kept(sum(a.size * a.dtype.itemsize
                                  for a in avals))
    return kept


def recompute_layer(layer: Callable) -> Callable:
    """``layer`` under ``jax.checkpoint``, keeping the results named in
    ``KEPT_NAMES``; call it in the forward pass that uses it."""
    # a closure of this call's own: jax.checkpoint keeps what it traced
    # by function and shapes, and a layer reads its parameters from the
    # call that traces it (functional_call), so a second trace at the
    # same shapes must not find the first
    def primal(*args):
        with xprof.layer_primal():
            return layer(*args)
    return jax.checkpoint(primal, policy=_kept)
