"""LocalSGD: per-replica local updates with periodic parameter averaging.

TPU-native rebuild of the reference's LocalSGD meta-optimizer
(/root/reference/python/paddle/distributed/fleet/meta_optimizers/
localsgd_optimizer.py: each worker steps locally, every k steps params are
allreduce-averaged). There each GPU process owns its own params; here the
replicas live in ONE SPMD program: every param carries a leading replica
axis sharded over ``dp``, local steps run under shard_map with **no
cross-replica collective**, and the sync step pmean-averages params (and
resets optimizer slots' divergence) over the dp axis. Two compiled
programs — Python picks sync every k-th call, mirroring the reference's
step-counter conditional block.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

from ..core import as_label_tuple
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ._shard_map import shard_map as _shard_map

from ..core import random as _random
from ..nn.layer import Layer, functional_call
from ..optimizer import Optimizer


class LocalSGDStep:
    """Train step with k-step local updates then cross-replica averaging.

    Batch layout: arrays with global batch leading dim, sharded over dp
    like ShardedTrainStep; each replica trains on its own shard.
    """

    def __init__(self, model: Layer, optimizer: Optimizer,
                 loss_fn: Callable, mesh: Mesh, k_steps: int = 4,
                 seed: int = 0, dp_axis: str = "dp") -> None:
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.k_steps = max(1, int(k_steps))
        self.axis = dp_axis
        self._calls = 0
        n = mesh.shape[dp_axis]
        self.n_replicas = n

        params = model.param_dict()
        buffers = model.buffer_dict()
        opt_state = optimizer.init(params)

        def stack(tree):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (n,) + tuple(x.shape)).astype(x.dtype)
                if hasattr(x, "ndim") else x, tree)

        # replica-stacked state: leading axis = replica, sharded over dp
        state = {
            "params": stack(params),
            "buffers": stack(buffers),
            "opt": {"step": opt_state["step"],
                    "slots": stack(opt_state["slots"])},
            "rng": jax.random.split(_random.make_key(seed), n),
        }

        def rep_spec(tree):
            return jax.tree.map(
                lambda x: P(dp_axis) if hasattr(x, "ndim") and x.ndim > 0
                else P(), tree)

        self.state_specs = {
            "params": rep_spec(state["params"]),
            "buffers": rep_spec(state["buffers"]),
            "opt": {"step": P(), "slots": rep_spec(state["opt"]["slots"])},
            "rng": P(dp_axis),
        }
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self.state_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        self.state = jax.device_put(state, shardings)
        self.batch_sharding = NamedSharding(mesh, P(dp_axis))

        from .spmd import host_lr_of
        self._host_lr_active = host_lr_of(optimizer) is not None

        def local_step(state, batch, rep_kwargs, lr):
            # inside shard_map: leading replica axis is size 1 locally
            def unstack(tree):
                return jax.tree.map(
                    lambda x: x[0] if hasattr(x, "ndim") and x.ndim > 0
                    else x, tree)

            def restack(tree):
                return jax.tree.map(
                    lambda x: x[None] if hasattr(x, "ndim") else x, tree)

            params = unstack(state["params"])
            buffers = unstack(state["buffers"])
            slots = unstack(state["opt"]["slots"])
            rng = state["rng"][0]
            rng, step_key = jax.random.split(rng)

            def loss_of(p):
                with _random.rng_scope(default=step_key, dropout=step_key):
                    out, new_buffers = functional_call(
                        self.model, p, buffers, *batch["args"],
                        **batch.get("kwargs", {}), **rep_kwargs,
                        capture_buffers=True)
                return self.loss_fn(out, *batch["labels"]), new_buffers

            (loss, new_buffers), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            new_params, new_opt = self.optimizer.apply_gradients(
                params, grads, {"step": state["opt"]["step"],
                                "slots": slots},
                lr_override=lr if self._host_lr_active else None)
            # mean loss across replicas for reporting only
            loss = lax.pmean(loss, dp_axis)
            return ({"params": restack(new_params),
                     "buffers": restack(new_buffers),
                     "opt": {"step": new_opt["step"],
                             "slots": restack(new_opt["slots"])},
                     "rng": rng[None]}, {"loss": loss})

        def sync(state):
            # average params across replicas (ref: localsgd_optimizer.py
            # allreduce(param)/nranks); optimizer slots averaged too so
            # replicas restart from identical state
            def avg(tree):
                return jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        lax.pmean(x, dp_axis), x.shape)
                    if hasattr(x, "ndim") and x.ndim > 0 else x, tree)

            return {**state, "params": avg(state["params"]),
                    "opt": {"step": state["opt"]["step"],
                            "slots": avg(state["opt"]["slots"])}}

        smap = dict(mesh=mesh, check_vma=False)
        # host-driven LR rides as its own replicated scalar argument — a
        # rank-0 leaf can't satisfy the batch's P(dp_axis) shard_map spec
        self._local = jax.jit(
            _shard_map(local_step,
                          in_specs=(self.state_specs, P(dp_axis), P(),
                                    P()),
                          out_specs=(self.state_specs, P()), **smap),
            donate_argnums=(0,))
        self._sync = jax.jit(
            _shard_map(sync, in_specs=(self.state_specs,),
                          out_specs=self.state_specs, **smap),
            donate_argnums=(0,))

    def __call__(self, *args, labels=(), **kwargs):
        from .spmd import host_lr_of
        from .spmd import (leading_batch_size,
                           split_kwargs_by_shardable)
        # model-forward kwargs: dp-shardable leaves (leading dim
        # divisible by the dp size) ride the batch tree; the rest
        # (broadcast masks, tables, scalars) go replicated — the same
        # split ShardedTrainStep._place_batch makes
        sh_kwargs, rep_kwargs = split_kwargs_by_shardable(
            kwargs, leading_batch_size(args, labels),
            note=self.mesh.shape[self.axis] > 1)
        batch = {"args": args, "labels": as_label_tuple(labels),
                 "kwargs": sh_kwargs}
        lr = host_lr_of(self.optimizer) if self._host_lr_active else 0.0
        with self.mesh:
            self.state, metrics = self._local(self.state, batch,
                                              rep_kwargs,
                                              jnp.float32(lr))
            self._calls += 1
            if self._calls % self.k_steps == 0:
                self.state = self._sync(self.state)
        return metrics

    def averaged_params(self) -> Dict:
        """Replica-mean parameters (what the synced model would hold)."""
        return jax.tree.map(
            lambda x: jnp.mean(x, axis=0) if hasattr(x, "ndim") and
            x.ndim > 0 else x, self.state["params"])

    def replica_divergence(self) -> float:
        """Max abs spread across replicas — 0 right after a sync."""
        div = 0.0
        for v in jax.tree.leaves(self.state["params"]):
            if hasattr(v, "ndim") and v.ndim > 0:
                spread = jnp.max(jnp.abs(v - v[0:1]))
                div = max(div, float(spread))
        return div
