"""Pipeline parallelism.

TPU-native redesign of the reference's pipeline stack
(/root/reference/python/paddle/fluid/optimizer.py:3627 PipelineOptimizer
splits the program by device_guard into section programs;
framework/pipeline_trainer.cc:24 + section_worker.cc:82 run sections in
threads, passing tensors via queues/condvars). That thread/queue schedule
doesn't map to XLA; the TPU idiom is **SPMD pipelining inside one compiled
program**: every device holds one stage's params (stacked pytree sharded on
a 'pp' mesh axis), and a fori_loop runs the GPipe schedule where activations
hop stage→stage via lax.ppermute over ICI. Bubbles are the standard
(S-1)/(M+S-1) GPipe fraction; microbatch count M trades bubble for memory.

The stage function must be shape-preserving (transformer-trunk style);
embedding/head run outside the pipeline (as the reference runs the reader
and loss sections on first/last devices).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

from ..core import as_label_tuple
import jax

from ..core import random as _random
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ._shard_map import shard_map as _shard_map

from ..nn.layer import Layer, functional_call


def stack_stage_params(stage_layers: Sequence[Layer]):
    """Stack per-stage param dicts along a new leading 'stage' axis.

    All stages must share one structure (homogeneous trunk)."""
    dicts = [l.param_dict() for l in stage_layers]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *dicts)


def gpipe(stage_fn: Callable, stacked_params, x, num_microbatches: int,
          mesh: Mesh, axis: str = "pp"):
    """Run the GPipe schedule over the 'pp' mesh axis.

    stage_fn(params_slice, x_mb) -> y_mb, shape-preserving.
    stacked_params: pytree with leading dim == n_stages (sharded on axis).
    x: [B, ...] with B divisible by num_microbatches.
    Returns y with x's shape: the composition of all stages.
    """
    n_stages = mesh.shape[axis]
    m = num_microbatches
    b = x.shape[0]
    mb = b // m
    micro = x.reshape((m, mb) + x.shape[1:])

    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def spmd_fn(params, micro_all):
        # params leaves: [1, ...] (this device's stage); squeeze stage dim
        local = jax.tree.map(lambda p: p[0], params)
        stage_id = lax.axis_index(axis)
        is_first = stage_id == 0
        is_last = stage_id == n_stages - 1

        zero_mb = jnp.zeros_like(micro_all[0])
        outputs0 = jnp.zeros_like(micro_all)

        def tick(t, carry):
            recv, outputs = carry
            # stage 0 consumes microbatch t (while valid); others consume
            # what arrived from the previous stage last tick
            idx = jnp.minimum(t, m - 1)
            inp = jnp.where(is_first, micro_all[idx], recv)
            out = stage_fn(local, inp)
            # last stage records its result for microbatch t-(S-1)
            out_idx = t - (n_stages - 1)
            valid_out = jnp.logical_and(is_last, out_idx >= 0)
            outputs = lax.cond(
                valid_out,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(out_idx, 0), axis=0),
                lambda o: o, outputs)
            recv_next = lax.ppermute(out, axis, fwd_perm)
            return (recv_next, outputs)

        _, outputs = lax.fori_loop(0, m + n_stages - 1, tick,
                                   (zero_mb, outputs0))
        # replicate the last stage's outputs to all devices: zero elsewhere
        # then psum over the stage axis
        outputs = jnp.where(is_last, outputs, jnp.zeros_like(outputs))
        return lax.psum(outputs, axis)

    param_specs = jax.tree.map(lambda _: P(axis), stacked_params)
    out = _shard_map(
        spmd_fn, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, micro)
    return out.reshape((b,) + x.shape[1:])


class GPipeTrainStep:
    """Full pipeline-parallel training step: embed → pipelined trunk →
    head, jax.grad through the whole schedule, optimizer update.

    Replaces PipelineOptimizer + PipelineTrainer + SectionWorker for the
    TPU: one compiled program, grads flow backward through the same
    ppermute schedule automatically (XLA transposes ppermute).
    """

    def __init__(self, embed: Layer, stage_layers: Sequence[Layer],
                 head: Layer, optimizer, loss_fn: Callable, mesh: Mesh,
                 num_microbatches: int, axis: str = "pp",
                 remat_stages: bool = False, seed: int = 0) -> None:
        self.embed = embed
        self.head = head
        self.stage_layers = list(stage_layers)
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.m = num_microbatches
        self.axis = axis
        n_stages = mesh.shape[axis]
        assert len(self.stage_layers) == n_stages, \
            f"need {n_stages} stages, got {len(self.stage_layers)}"

        params = {
            "embed": embed.param_dict(),
            "stages": stack_stage_params(self.stage_layers),
            "head": head.param_dict(),
        }
        opt_state = optimizer.init(params)
        stage_spec = jax.tree.map(lambda _: P(axis), params["stages"])
        self.param_specs = {
            "embed": jax.tree.map(lambda _: P(), params["embed"]),
            "stages": stage_spec,
            "head": jax.tree.map(lambda _: P(), params["head"]),
        }
        opt_slot_specs = {
            "step": P(),
            "slots": {
                "embed": jax.tree.map(lambda _: P(),
                                      opt_state["slots"]["embed"]),
                "stages": jax.tree.map(
                    lambda x: P(axis) if hasattr(x, "ndim") and x.ndim > 0
                    else P(), opt_state["slots"]["stages"]),
                "head": jax.tree.map(lambda _: P(),
                                     opt_state["slots"]["head"]),
            },
        }
        self.state_specs = {"params": self.param_specs,
                            "opt": opt_slot_specs, "rng": P()}
        state = {"params": params, "opt": opt_state,
                 "rng": _random.make_key(seed)}
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self.state_specs,
                                 is_leaf=lambda s: isinstance(s, P))
        self.state = jax.device_put(state, shardings)
        self._jitted = jax.jit(self._step, donate_argnums=(0,),
                               in_shardings=(shardings, None),
                               out_shardings=(shardings, None))

        template = self.stage_layers[0]

        def stage_fn(stage_params, x_mb):
            return functional_call(template, stage_params, None, x_mb)

        if remat_stages:
            # GPipe's peak lives in the stored per-microbatch stage
            # activations; rematerializing the stage body trades one
            # extra stage forward in the backward pass for dropping
            # those intermediates — the reference exposes the same knob
            # as recompute+pipeline (DistributedStrategy.recompute)
            stage_fn = jax.checkpoint(stage_fn)
        self._stage_fn = stage_fn

    def _forward(self, params, x):
        h = functional_call(self.embed, params["embed"], None, x)
        h = gpipe(self._stage_fn, params["stages"], h, self.m, self.mesh,
                  self.axis)
        return functional_call(self.head, params["head"], None, h)

    def _step(self, state, batch):
        rng, _ = jax.random.split(state["rng"])

        def loss_of(p):
            out = self._forward(p, batch["x"])
            return self.loss_fn(out, *batch["labels"])

        loss, grads = jax.value_and_grad(loss_of)(state["params"])
        new_params, new_opt = self.optimizer.apply_gradients(
            state["params"], grads, state["opt"],
            lr_override=batch.get("lr"))
        return ({"params": new_params, "opt": new_opt, "rng": rng},
                {"loss": loss})

    def __call__(self, x, labels=()):
        from .spmd import inject_host_lr
        batch = inject_host_lr({"x": x, "labels": as_label_tuple(labels)},
                               self.optimizer)
        with self.mesh:
            self.state, metrics = self._jitted(self.state, batch)
        return metrics
