"""Mixture-of-Experts layer with expert parallelism.

The reference predates MoE (SURVEY §2.8 marks EP "absent in this
reference; cheap extension under pjit"), but the capability class it
covers — sharding a huge parameter space across devices, the role its
PS sharded embeddings play — is idiomatic on TPU as an expert-parallel
einsum: experts live stacked on a leading [E, ...] axis sharded over
the mesh's "ep" axis, tokens are dispatched densely with a capacity
limit (one-hot einsum — static shapes, MXU-friendly), and XLA inserts
the all-to-alls from the sharding annotations (the same mechanism the
reference's NCCL graph passes hand-build).

Two layers live here. :class:`MoELayer` is that GShard layer: softmax
gate, ``capacity_factor``, overflow tokens dropped, every expert held;
it stays for what it alone does, a layer whose experts GSPMD spreads
over an ``ep`` mesh axis from sharding annotations
(:func:`moe_param_rule`), where static capacity is what lets XLA insert
the exchange. :class:`DroplessMoE` is the layer of today's sparse
language models and of one expert-parallel rank: a sigmoid (or softmax)
router with a selection-only bias, no capacity and no dropped pair, a
shared expert, and a sort by expert with a grouped matmul over the
experts held here, two-matrix ``relu^2`` or gated three-matrix ones.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ...core.dtype import get_default_dtype
from ...observability import xprof
from .. import initializer as I
from ..layer import Layer, Parameter

__all__ = ["MoELayer", "DroplessMoE", "moe_param_rule"]


class MoELayer(Layer):
    """Top-k gated MoE FFN (Switch/GShard style).

    x [B, T, D] → gate picks top_k of num_experts per token; each
    expert is a 2-layer FFN with stacked weights [E, D, H]/[E, H, D].
    Dense dispatch with ``capacity_factor``: each expert processes at
    most ceil(tokens/E * cf) tokens, overflow tokens are dropped
    (standard GShard semantics; keeps every shape static for XLA).
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 activation: str = "gelu") -> None:
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.top_k = min(top_k, num_experts)
        self.capacity_factor = capacity_factor
        dtype = get_default_dtype()
        init = I.XavierUniform()
        self.gate_weight = Parameter(
            init((d_model, num_experts), dtype))
        self.w_in = Parameter(init((num_experts, d_model, d_hidden),
                                   dtype))
        self.b_in = Parameter(jnp.zeros((num_experts, d_hidden), dtype))
        self.w_out = Parameter(init((num_experts, d_hidden, d_model),
                                    dtype))
        self.b_out = Parameter(jnp.zeros((num_experts, d_model), dtype))
        # threaded out through functional_call's buffer capture (a plain
        # attribute would leak a tracer under jit); to TRAIN with it,
        # return it from your model and add weight*aux in loss_fn
        self.register_buffer("aux_loss", jnp.zeros((), jnp.float32))
        from ...ops import activation as A
        self._act = getattr(A, activation)

    def forward(self, x):
        b, t, d = x.shape
        n_tok = b * t
        e = self.num_experts
        cap = max(1, math.ceil(
            self.capacity_factor * n_tok * self.top_k / e))
        tokens = x.reshape(n_tok, d)

        logits = tokens @ self.gate_weight  # [N, E]
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, self.top_k)  # [N, k]

        # position of each (token, choice) within its expert's queue:
        # count prior assignments to the same expert (GShard cumsum)
        choice_onehot = jax.nn.one_hot(top_e, e, dtype=jnp.float32)
        # flatten choices in priority order: all k=0 choices first
        flat = choice_onehot.transpose(1, 0, 2).reshape(
            self.top_k * n_tok, e)
        pos_flat = jnp.cumsum(flat, axis=0) - flat  # prior count
        position = (pos_flat * flat).sum(-1).reshape(
            self.top_k, n_tok).transpose(1, 0)  # [N, k]
        keep = position < cap

        pos_onehot = jax.nn.one_hot(position, cap,
                                    dtype=jnp.float32)  # [N, k, C]
        # dispatch[n, e, c] = Σ_k choice[n,k,e]·keep[n,k]·pos[n,k,c]
        dispatch = jnp.einsum("nke,nk,nkc->nec", choice_onehot,
                              keep.astype(jnp.float32), pos_onehot)

        expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                               tokens.astype(jnp.float32))
        expert_in = expert_in.astype(x.dtype)  # [E, C, D]
        h = self._act(jnp.einsum("ecd,edh->ech", expert_in, self.w_in)
                      + self.b_in[:, None])
        out = jnp.einsum("ech,ehd->ecd", h, self.w_out) \
            + self.b_out[:, None]  # [E, C, D]

        gates = (top_p * keep).astype(jnp.float32)  # [N, k]
        combine = jnp.einsum("nke,nk,nkc->nec", choice_onehot, gates,
                             pos_onehot)
        y = jnp.einsum("nec,ecd->nd", combine,
                       out.astype(jnp.float32)).astype(x.dtype)

        # load-balance auxiliary loss (GShard): mean gate prob x mean
        # assignment fraction per expert, scaled by E
        frac_tokens = choice_onehot[:, 0].mean(axis=0)  # top-1 fraction
        mean_prob = probs.mean(axis=0)
        self.aux_loss = e * jnp.sum(frac_tokens * mean_prob)
        return y.reshape(b, t, d)


# The routed part runs on (token, choice) pairs sorted by expert. Every
# pair may fall on the experts held here, so the sorted list is tokens x
# top_k long; a rank that holds held/total of the experts sees that
# share of it when the router is balanced. The list is therefore walked
# in windows of WINDOW_FACTOR times the balanced load. A window that
# holds a held pair is computed whole (the rows past the last pair are
# zeros in the last group), and one that starts past the last held pair
# is never reached (the loop over windows is bounded by the step's own
# count of held pairs, and an empty window costs nothing): a step whose
# held pairs fit one window takes the same time whatever the routing,
# the worst routing runs every window, no pair is dropped either way,
# and the buffers are one window's. A window's products go through one
# seam, `kernels.maybe_grouped_matmul`: on a TPU the repo's `moe_gmm` /
# `moe_tgmm` kernels (kernels/grouped_matmul.py), which walk a window in
# row tiles that divide _ROW_TILE; elsewhere `jax.lax.ragged_dot`.
WINDOW_FACTOR = 2
_ROW_TILE = 512


class DroplessMoE(Layer):
    """Sigmoid-routed experts with a shared expert, for a layer that
    holds ``experts_held`` of ``num_experts`` experts starting at
    ``expert_offset`` (all of them by default).

    ``s = sigmoid(W_r x)`` in float32; the ``top_k`` largest of
    ``s + e_score_correction_bias`` choose (the bias is a buffer and
    only selects); ``w = scale * s[chosen] / (sum s[chosen] + 1e-20)``
    (the division only with ``norm_topk_prob``); ``out = sum_e w_e W2_e
    relu(W1_e x)^2 + Ws2 relu(Ws1 x)^2``. The router scores every
    expert; pairs that fall on experts held elsewhere add nothing here
    (on an expert-parallel rank their part arrives by the exchange,
    which this layer does not contain). The held pairs are sorted by
    expert and run, a window of the sorted list at a time, through one
    pair of grouped matmuls (``kernels.maybe_grouped_matmul`` with the
    true group sizes, a window's empty rows zeros in its last group: on
    a TPU the Pallas kernels ``moe_gmm`` and ``moe_tgmm`` of
    kernels/grouped_matmul.py, elsewhere ``jax.lax.ragged_dot``): no
    capacity, no dropped pair, whatever the routing. A window is twice the
    balanced load and is computed whole, so a balanced layer multiplies
    as many rows of zeros as rows of pairs: a step's time follows the
    number of windows that hold a pair, not the pairs (PERF.md section
    6, PR 29, has the price and why it is paid).

    Options, each off by default (the layer then traces to what it
    always did). ``score_func="softmax"`` scores by ``softmax(W_r x)``
    over all the experts in sigmoid's place (selection, bias, the
    division and the scale as above). ``gated=True`` makes an expert
    three matrices, ``W_down (act(W_gate x) * W_up x)``: ``w_in`` is
    then ``[held, d_model, 2 d_expert]``, gate columns first, up columns
    after, one operand so that a window's first product is one grouped
    matmul as it is without the gate (``w_out`` is ``W_down``); the
    shared expert, where there is one, is gated alike. ``gate_act`` is
    that gate's ``act``: ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU).
    ``forward(x, router_input)`` routes on ``router_input`` (a tensor of
    ``x``'s shape: a layer whose router reads another tensor than its
    experts do) and sends ``x`` to the experts chosen.

    Returns ``(out, stats)``; ``stats`` holds the scalars
    ``pairs_held`` (pairs on held experts in this call),
    ``load_max_over_mean`` (the fullest held expert over their mean),
    ``pairs_dropped`` (held pairs no window covers: 0) and
    ``windows_run`` (the windows that hold a held pair, which are the
    trips of the loop over windows, forward and backward: ``ceil(
    pairs_held / rows)``, 1 for a balanced layer, 0 when nothing is
    held), and
    ``expert_load`` [num_experts], the pairs this call's tokens sent to
    each expert the router scores: what the aux-loss-free balancing
    rule reads to move ``e_score_correction_bias`` (the layer itself
    never writes the buffer).

    The routing plan carries names: ``moe_chosen`` (the chosen experts
    [N, k]), ``moe_order`` (the pairs sorted by expert) and ``moe_load``
    (``expert_load``), all int32. Inside ``nn.recompute_layer`` the
    backward pass reads the kept three and runs no second ``top_k``,
    sort or count; the router's matmul, the scores and the weights it
    recomputes, since their gradient needs them."""

    def __init__(self, d_model: int, d_expert: int, num_experts: int,
                 top_k: int, d_shared: int = 0,
                 experts_held: Optional[int] = None,
                 expert_offset: int = 0,
                 routed_scaling_factor: float = 1.0,
                 norm_topk_prob: bool = True, weight_attr=None,
                 out_weight_attr=None, score_func: str = "sigmoid",
                 gated: bool = False, gate_act: str = "silu") -> None:
        super().__init__()
        if score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"score_func {score_func!r}")
        if gate_act not in ("silu", "relu"):
            raise ValueError(f"gate_act {gate_act!r}")
        self.score_func, self.gated = score_func, gated
        self.gate_act = gate_act
        held = num_experts if experts_held is None else experts_held
        if not (0 <= expert_offset and expert_offset + held <= num_experts
                and 0 < top_k <= num_experts):
            raise ValueError(
                f"experts [{expert_offset}, {expert_offset + held}) of "
                f"{num_experts}, top_k {top_k}")
        self.num_experts, self.top_k = num_experts, top_k
        self.experts_held, self.expert_offset = held, expert_offset
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        dtype = get_default_dtype()
        self.router_weight = I.make_param(
            weight_attr, I.XavierUniform(), (d_model, num_experts), dtype)
        self.register_buffer("e_score_correction_bias",
                             jnp.zeros((num_experts,), jnp.float32))
        self.w_in = I.make_param(
            weight_attr, I.XavierUniform(),
            (held, d_model, d_expert * (2 if gated else 1)), dtype)
        self.w_out = I.make_param(out_weight_attr, I.XavierUniform(),
                                  (held, d_expert, d_model), dtype)
        self.has_shared = d_shared > 0
        if self.has_shared:
            from .common import Linear
            self.shared_in = Linear(d_model, d_shared * (2 if gated
                                                         else 1),
                                    weight_attr, bias_attr=False)
            self.shared_out = Linear(d_shared, d_model, out_weight_attr,
                                     bias_attr=False)

    def _act(self, h):
        """An expert's activation on its first product [rows, width]."""
        if not self.gated:
            return jnp.square(jax.nn.relu(h))
        gate, up = jnp.split(h, 2, axis=-1)
        if self.gate_act == "relu":
            return jax.nn.relu(gate) * up
        return jax.nn.silu(gate) * up

    def route(self, tokens):
        """(chosen experts [N, k] int32, their weights [N, k] float32)."""
        logits = jnp.matmul(
            tokens.astype(jnp.float32),
            self.router_weight.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits) if self.score_func == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(
            scores + self.e_score_correction_bias, self.top_k)
        chosen = checkpoint_name(chosen, "moe_chosen")
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        if self.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return chosen, w * self.routed_scaling_factor

    def _window(self, acc, tokens, weights, w_in, w_out, order, ends, lo,
                rows: int):
        """``acc`` [N, D] float32 and what the held pairs ``order[lo:lo +
        rows]`` add to it. ``ends`` are the cumulative group sizes."""
        from ...kernels import maybe_group_tiles, maybe_grouped_matmul
        with jax.named_scope("pt.moe_route"):
            pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
            token_of = pair // self.top_k
            inside = jnp.clip(ends, lo, lo + rows) - lo
            live = jnp.arange(rows) < inside[-1]
            # the rows past the last held pair are zeros and join the
            # last group: a window is computed whole whatever share of
            # it holds pairs, so its time does not follow the routing
            sizes = jnp.diff(inside, prepend=0).at[-1].add(
                rows - inside[-1])
            rows_in = tokens[token_of]
            w = jnp.where(live, weights.reshape(-1)[pair], 0.0)
            # what the window's products share (None off a TPU)
            tiles = maybe_group_tiles(sizes, rows)
        with jax.named_scope("pt.moe_experts"):
            hidden = self._act(maybe_grouped_matmul(
                jnp.where(live[:, None], rows_in, 0), w_in, sizes, tiles))
            out = (maybe_grouped_matmul(hidden, w_out, sizes, tiles)
                   * w[:, None].astype(hidden.dtype)).astype(jnp.float32)
        with jax.named_scope("pt.moe_route"):
            return acc.at[token_of].add(out)

    def _routed(self, tokens, weights, order, ends, rows: int, windows):
        """The held experts' part, [N, D] float32: the sum of the first
        ``windows`` windows of the sorted list (the step's own count of
        those that hold a held pair). Differentiated by hand, a window
        at a time: the trip count is the step's, which reverse-mode AD
        cannot walk back, and a scan over every window keeps a [N, D]
        residual for each, run or not."""
        def loop_windows(add_window, init, trips):
            @jax.named_scope("pt.moe_route")    # the body's own stack
            def body(i, acc):
                return add_window(acc, i * rows)
            return jax.lax.fori_loop(0, trips, body, init)

        primal_traced = []

        @jax.custom_vjp
        @jax.named_scope("pt.moe_route")    # the accumulator's zeros
        def routed(diff, order, ends, trips):
            primal_traced.append(True)
            return loop_windows(
                lambda acc, lo: self._window(acc, *diff, order, ends, lo,
                                             rows),
                jnp.zeros(tokens.shape, jnp.float32), trips)

        def forward(*args):
            # traced after the primal, this rule is `jax.checkpoint`'s
            # recomputation: nothing after the mixer reads its result,
            # so its products are dead code and not call sites that run
            # (without a checkpoint it is the one forward, and notes)
            with xprof.unnoted(when=bool(primal_traced)):
                return routed(*args), args

        @jax.named_scope("pt.moe_route")
        def backward(saved, g):
            diff, order, ends, trips = saved

            def add_window(acc, lo):
                # the window's result is not wanted here, so neither is
                # what it would be added to
                _, pull = jax.vjp(lambda *d: self._window(
                    jnp.zeros_like(g), *d, order, ends, lo, rows), *diff)
                grads = pull(g)
                per_token = jax.tree.map(jnp.add, acc[:2], grads[:2])
                # the weight gradients' sums belong with their products
                with jax.named_scope("pt.moe_experts"):
                    return per_token + jax.tree.map(jnp.add, acc[2:],
                                                    grads[2:])

            summed = loop_windows(
                add_window, jax.tree.map(jnp.zeros_like, diff), trips)
            return summed, None, None, None

        routed.defvjp(forward, backward)
        # the count is traced: an argument, since a custom_vjp function
        # may not close over a tracer
        return routed((tokens, weights, self.w_in, self.w_out), order,
                      ends, jnp.asarray(windows, jnp.int32))

    def forward(self, x, router_input=None):
        tokens = x.reshape(-1, x.shape[-1])
        n, held = tokens.shape[0], self.experts_held
        total = n * self.top_k
        rows = min(total, -(-WINDOW_FACTOR * total * held
                            // (self.num_experts * _ROW_TILE)) * _ROW_TILE)
        windows = -(-total // rows)
        with jax.named_scope("pt.moe_route"):
            chosen, weights = self.route(
                tokens if router_input is None
                else router_input.reshape(tokens.shape))
            local = chosen - self.expert_offset
            # absent experts sort last, into a group no matmul visits
            key = jnp.where((local >= 0) & (local < held), local,
                            held).reshape(-1)
            order = checkpoint_name(
                jnp.pad(jnp.argsort(key), (0, windows * rows - total)),
                "moe_order")
            load = checkpoint_name(
                jnp.bincount(chosen.reshape(-1), length=self.num_experts)
                .astype(jnp.int32), "moe_load")
            held_load = load[self.expert_offset:self.expert_offset + held]
            ends = jnp.cumsum(held_load)
            pairs_held = ends[-1]
            windows_run = jnp.minimum(-(-pairs_held // rows), windows)
        routed = self._routed(tokens, weights, order, ends, rows,
                              windows_run)
        out = routed.astype(x.dtype)
        if self.has_shared:
            with jax.named_scope("pt.moe_shared"):
                out = out + self.shared_out(self._act(
                    self.shared_in(tokens)))
        stats = {
            "pairs_held": pairs_held,
            "load_max_over_mean": jnp.max(held_load) * held / jnp.maximum(
                pairs_held, 1).astype(jnp.float32),
            # every window that holds a held pair runs
            "pairs_dropped": jnp.maximum(pairs_held - windows * rows, 0),
            "windows_run": windows_run,
            "expert_load": load,
        }
        return out.reshape(x.shape), stats


def moe_param_rule(ep_axis: str = "ep"):
    """param_rule for ShardedTrainStep: shard the stacked expert
    dimension over the ep mesh axis (XLA turns the dispatch/combine
    einsums into all-to-alls across it)."""
    from jax.sharding import PartitionSpec as P

    def rule(name: str, v) -> P:
        shape = getattr(v, "shape", ())
        leaf = name.split(".")[-1]
        if leaf in ("w_in", "w_out", "b_in", "b_out") \
                and len(shape) >= 2:
            return P(ep_axis, *([None] * (len(shape) - 1)))
        return P()

    return rule
