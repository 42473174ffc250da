"""Nemotron-H: a pre-norm decoder whose every layer is one mixer behind
a residual, the kind of each layer read from a pattern string — ``M`` a
Mamba-2 mixer, ``E`` sigmoid-routed experts with a shared expert, ``*``
causal grouped-query attention without a positional term (HF
``modeling_nemotron_h.py``; NVIDIA-Nemotron-3-Nano-30B-A3B is the
published instance: 52 layers of hidden size 2688, 128 experts).

``n_routed_experts`` counts the experts this model instance HOLDS; with
``n_routed_experts_total`` larger, the model is one expert-parallel
rank's share: the router scores all ``n_routed_experts_total``, and the
experts ``[expert_offset, expert_offset + n_routed_experts)`` add their
part (``nn.DroplessMoE``). ``vocab_size`` likewise is the rows held.

With ``recompute="layer"`` a training step keeps every layer's input,
a ``*`` layer's flash result and an ``E`` layer's routing plan
(``nn.recompute_layer``), and makes the rest of the layer again in the
backward pass; an ``M`` layer keeps its input alone.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import initializer as I

__all__ = ["NemotronHConfig", "NemotronHForCausalLM", "CausalLMOutput",
           "next_token_loss", "routing_metrics", "balance_router_bias"]


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = \
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # M
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # E
    n_routed_experts: int = 128
    n_routed_experts_total: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # aux-loss-free balancing (Wang et al. 2024, as DeepSeek-V3 trains):
    # after every training step each expert layer's selection bias moves
    # by this much towards the experts that got fewer pairs than the
    # mean. 0 leaves the bias where it is.
    router_bias_update_rate: float = 0.0
    # *
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    # "layer": while training, every layer is recomputed in the
    # backward pass from its input, but for the flash kernel's result
    # and the routing plan, which are kept (nn.recompute_layer)
    recompute: str = "none"

    def __post_init__(self) -> None:
        if set(self.hybrid_override_pattern) - set("ME*"):
            raise ValueError("hybrid_override_pattern is made of M, E "
                             f"and *: {self.hybrid_override_pattern!r}")
        if self.recompute not in ("none", "layer"):
            raise ValueError(f"recompute {self.recompute!r}")


class CausalLMOutput(NamedTuple):
    """What the model hands its loss: the final hidden states and the
    head's weight instead of [B, S, V] logits (``next_token_loss``
    streams the projection in blocks of tokens), and the step's routing
    counters, summed (the ratio: largest) over the ``E`` layers."""
    hidden: jnp.ndarray
    head_weight: jnp.ndarray
    moe_pairs_held: jnp.ndarray
    moe_load_max_over_mean: jnp.ndarray
    moe_pairs_dropped: jnp.ndarray
    # [expert layers, experts scored]: pairs sent to each expert
    moe_expert_load: jnp.ndarray
    # trips of the expert layers' loops over windows (those that hold a
    # held pair), forward; the backward makes as many
    moe_windows_run: jnp.ndarray

    def logits(self):
        return jnp.matmul(self.hidden, self.head_weight)


# the scope a block's norm and residual add are charged to
_BLOCK_SCOPE = {"M": "pt.ssm_proj", "E": "pt.moe_route", "*": "pt.attn"}


class NemotronHBlock(nn.Layer):
    """``x + mixer(rmsnorm(x))`` for one character of the pattern."""

    def __init__(self, config: NemotronHConfig, kind: str) -> None:
        super().__init__()
        c = self.config = config
        self.kind = kind
        w = I.Normal(0.0, c.initializer_range)
        # rescale_prenorm_residual: projections into the residual
        out_w = I.Normal(0.0, c.initializer_range / math.sqrt(
            len(c.hybrid_override_pattern)))
        self.norm = nn.RMSNorm(c.hidden_size, c.layer_norm_epsilon)
        if kind == "M":
            self.mixer = nn.Mamba2Mixer(
                c.hidden_size, c.mamba_num_heads, c.mamba_head_dim,
                c.ssm_state_size, c.n_groups, c.conv_kernel, c.chunk_size,
                c.layer_norm_epsilon, c.time_step_min, c.time_step_max,
                c.time_step_floor, weight_attr=w, out_weight_attr=out_w)
        elif kind == "E":
            self.mixer = nn.DroplessMoE(
                c.hidden_size, c.moe_intermediate_size,
                c.n_routed_experts_total or c.n_routed_experts,
                c.num_experts_per_tok,
                d_shared=c.moe_shared_expert_intermediate_size,
                experts_held=c.n_routed_experts,
                expert_offset=c.expert_offset,
                routed_scaling_factor=c.routed_scaling_factor,
                norm_topk_prob=c.norm_topk_prob, weight_attr=w,
                out_weight_attr=out_w)
        else:
            self.mixer = nn.GroupedQueryAttention(
                c.hidden_size, c.num_attention_heads,
                c.num_key_value_heads, c.head_dim, causal=True,
                weight_attr=w, out_weight_attr=out_w)

    def forward(self, x):
        scope = _BLOCK_SCOPE[self.kind]
        with jax.named_scope(scope):
            h = self.norm(x)
        stats = None
        if self.kind == "E":
            y, stats = self.mixer(h)
        elif self.kind == "*":
            with jax.named_scope(scope):
                y = self.mixer(h)
        else:
            y = self.mixer(h)
        with jax.named_scope(scope):
            return x + y, stats


class NemotronHForCausalLM(nn.Layer):
    """Token embedding, the blocks of the pattern, a final RMSNorm and
    an untied head."""

    def __init__(self, config: Optional[NemotronHConfig] = None) -> None:
        super().__init__()
        c = self.config = config or NemotronHConfig()
        w = I.Normal(0.0, c.initializer_range)
        self.embeddings = nn.Embedding(c.vocab_size, c.hidden_size,
                                       weight_attr=w)
        self.layers = nn.LayerList([NemotronHBlock(c, kind) for kind
                                    in c.hybrid_override_pattern])
        self.norm_f = nn.RMSNorm(c.hidden_size, c.layer_norm_epsilon)
        self.lm_head = nn.Linear(c.hidden_size, c.vocab_size,
                                 weight_attr=w, bias_attr=False)

    def router_bias_fit(self):
        """What ``balance_router_bias`` asks of a model: the names of
        its selection-bias buffers in the order of ``moe_expert_load``'s
        rows, and the fit's schedule: forward passes, the first and the
        last size of a move (a sigmoid score lies in (0, 1): 0.03
        crosses the scores' spread in a few rounds, 0.001 is a training
        step's move)."""
        names = [f"layers.{i}.mixer.e_score_correction_bias" for i, kind
                 in enumerate(self.config.hybrid_override_pattern)
                 if kind == "E"]
        return names, 48, 0.03, 0.001

    def forward(self, input_ids) -> CausalLMOutput:
        with jax.named_scope("pt.embed"):
            x = self.embeddings(input_ids)
        remat = self.config.recompute == "layer" and self.training
        held = jnp.zeros((), jnp.int32)
        dropped = jnp.zeros((), jnp.int32)
        windows_run = jnp.zeros((), jnp.int32)
        ratio = jnp.zeros((), jnp.float32)
        loads = []
        rate = self.config.router_bias_update_rate if self.training else 0
        for layer in self.layers:
            x, stats = (nn.recompute_layer(layer) if remat else layer)(x)
            if stats is None:
                continue
            held = held + stats["pairs_held"]
            dropped = dropped + stats["pairs_dropped"]
            windows_run = windows_run + stats["windows_run"]
            ratio = jnp.maximum(ratio, stats["load_max_over_mean"])
            loads.append(stats["expert_load"])
            if rate:
                # outside the recomputed layer: a buffer written there
                # would leak its tracer
                with jax.named_scope("pt.moe_route"):
                    layer.mixer.e_score_correction_bias = _balanced(
                        layer.mixer.e_score_correction_bias, loads[-1],
                        rate)
        with jax.named_scope("pt.head_loss"):
            x = self.norm_f(x)
        return CausalLMOutput(
            x, self.lm_head.weight, held, ratio, dropped,
            jnp.stack(loads) if loads else jnp.zeros((0, 0), jnp.int32),
            windows_run)


def _balanced(bias, load, rate):
    """One move of the aux-loss-free rule: up for the experts under the
    mean load, down for those over it."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def balance_router_bias(model, input_ids) -> float:
    """Fit every expert layer's ``e_score_correction_bias`` to
    ``input_ids`` [B, S]: forward passes (48 for this module's model),
    after each of which every layer's bias makes one move of the
    balancing rule, the moves shrinking geometrically (from 0.03 to
    0.001); the model's ``router_bias_fit()`` names the buffers and
    gives the schedule. It gives freshly initialised (or loaded) weights
    the balance that a training run's per-step moves reach after some
    thousands of steps. Writes the model's buffers; returns the fullest
    expert over the mean, the largest over the layers, after the last
    round."""
    from ..nn.layer import functional_call
    names, rounds, first, last = model.router_bias_fit()
    if not names:
        return 1.0

    @jax.jit
    def one_round(params, buffers, ids, rate):
        load = functional_call(model, params, buffers, ids).moe_expert_load
        moved = {n: _balanced(buffers[n], load[j], rate)
                 for j, n in enumerate(names)}
        worst = jnp.max(load.max(axis=1) / jnp.maximum(
            load.astype(jnp.float32).mean(axis=1), 1e-9))
        return {**buffers, **moved}, worst

    was_training = model.training
    model.eval()            # no recomputation, no per-step move
    try:
        params, buffers = model.param_dict(), model.buffer_dict()
        for r in range(rounds):
            rate = first * (last / first) ** (r / (rounds - 1))
            buffers, worst = one_round(params, buffers, input_ids,
                                       jnp.float32(rate))
    finally:
        model.train() if was_training else model.eval()
    slots = model._named_buffer_slots()
    for n in names:
        layer, bname = slots[n]
        layer._buffers[bname] = buffers[n]
    return float(worst)


# tokens whose logits exist at one time in the loss
_LOSS_BLOCK = 2048


@jax.named_scope("pt.head_loss")
def next_token_loss(out: CausalLMOutput, labels):
    """Mean cross-entropy of ``labels`` [B, S] (the token that follows
    each position) under the head's logits, float32. The projection and
    the softmax run over blocks of tokens, each recomputed in the
    backward pass, so [tokens, vocabulary] logits never exist whole."""
    hidden = out.hidden.reshape(-1, out.hidden.shape[-1])
    labels = labels.reshape(-1).astype(jnp.int32)
    n = hidden.shape[0]
    block = _LOSS_BLOCK if n % _LOSS_BLOCK == 0 else n

    @jax.checkpoint
    @jax.named_scope("pt.head_loss")    # a loop body's own name stack
    def block_nll(args):
        h, y = args
        logits = jnp.matmul(h, out.head_weight,
                            preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    nll = jax.lax.map(block_nll, (hidden.reshape(-1, block, hidden.shape[-1]),
                                  labels.reshape(-1, block)))
    return jnp.sum(nll) / n


def routing_metrics() -> Dict[str, Callable]:
    """``extra_metrics`` for ``static.TrainStep``: the routing counters
    of a step, returned beside its loss."""
    return {name: (lambda out, *labels, _n=name: getattr(out, _n))
            for name in ("moe_pairs_held", "moe_load_max_over_mean",
                         "moe_pairs_dropped", "moe_windows_run")}
