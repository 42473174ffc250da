"""``kind: sharded_train_step`` — BERT pretraining through
``parallel.ShardedTrainStep`` on the mesh the traffic mix names (data
parallel over ``dp``, Megatron column/row splits over ``mp``): the model
and the batches are ``train_step``'s, the window and its checks
``benchmarks/window.py``'s. What decides ``correct`` here is taken from
the mesh: the comparison with the reference runs the model as the step
placed it over the chips, and the state the window leaves is held to
what a mesh must keep (``check_parity_on_mesh``, ``check_replicas``)."""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from .. import arithmetic, generator
from ..harness import Run, log
from ..manifest import bench_module
from ..window import measure
from . import train_step


def check_parity_on_mesh(run: Run, step, model, batch) -> None:
    """The SHARDED program's loss and a few gradients against the plain
    float32 reference, dropout off, published widths, before the window.
    The parameters are the arrays the step spread over the mesh (the
    Megatron splits over ``mp``, the rest on every chip), the sample is
    one sequence a chip placed as the step places a batch (rows over
    ``dp``), and the program is traced under the step's mesh, so every
    routed kernel runs per shard and every exchange the partitioner or
    ``kernels._per_shard`` puts between the chips is in it: one made
    twice or left out moves the loss or a gradient. The reference runs
    on ONE chip from a gathered copy of the same values. The watched
    leaves are one split by columns, one by rows and one whole; the
    tolerances are the configuration file's."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import pretraining_loss
    from paddle_tpu.nn.layer import functional_call

    tol = run.config["tolerances"]
    cfg = run.config["model"]
    last = cfg["num_hidden_layers"] - 1
    leaves = [n.replace("{last}", str(last)) for n in tol["grad_leaves"]]
    params, buffers = step.state["params"], step.state["buffers"]
    rows = run.chips
    sample = tuple(np.asarray(a[:rows]) for a in batch)
    split = {k: str(params[k].sharding.spec) for k in leaves}
    log(f"mesh parity: {rows} sequences over {dict(step.mesh.shape)}, "
        f"watched leaves {split}")

    # parameters and the sample are ARGUMENTS of both programs (see
    # runners/train_step.py: a constant would be in the cache's key)
    def system(p, b, ids, pos, mlm, nsp):
        def loss_of(q):
            out = functional_call(model, q, b, ids, masked_positions=pos)
            return pretraining_loss(out, mlm, nsp).astype(jnp.float32)
        loss, g = jax.value_and_grad(loss_of)(p)
        return loss, {k: g[k] for k in leaves}

    ref = bench_module("references", run.cell["config"])

    def reference(watched, p, ids, pos, mlm, nsp):
        return ref.loss({**p, **watched}, cfg, ids, pos, mlm, nsp)

    model.eval()
    try:
        placed = tuple(jax.device_put(a, step.batch_sharding)
                       for a in sample)
        with jax.sharding.set_mesh(step.mesh):      # as the step traces
            sys_loss, sys_g = jax.jit(system)(params, buffers, *placed)
        sys_loss = float(sys_loss)
        sys_g = {k: np.asarray(v, np.float32) for k, v in sys_g.items()}
        one = run.devices[0]
        whole = jax.device_put(params, one)
        watched = {k: whole[k].astype(jnp.float32) for k in leaves}
        ref_loss, ref_g = jax.jit(jax.value_and_grad(reference))(
            watched, whole, *(jax.device_put(a, one) for a in sample))
    finally:
        model.train()
    ref_loss = float(ref_loss)
    worst = 0.0
    for k in leaves:
        b = np.asarray(ref_g[k], np.float32)
        rel = float(np.linalg.norm(sys_g[k] - b) / np.linalg.norm(b))
        worst = max(worst, rel)
        log(f"mesh parity grad {k}: rel l2 error {rel:.5f} "
            f"(|ref|={np.linalg.norm(b):.4e})")
    run.margins.update(parity_loss_abs=abs(sys_loss - ref_loss),
                       parity_grad_rel=worst)
    run.check(abs(sys_loss - ref_loss) <= tol["loss_abs"],
              f"mesh parity loss: sharded {sys_loss:.5f} vs reference "
              f"{ref_loss:.5f}, |diff| {abs(sys_loss - ref_loss):.5f} "
              f"<= {tol['loss_abs']}")
    run.check(worst <= tol["grad_rel_l2"],
              f"mesh parity gradients of {len(leaves)} leaves: worst rel "
              f"l2 error {worst:.5f} <= {tol['grad_rel_l2']}")


def check_replicas(run: Run, step) -> None:
    """What the timed program left on the chips: every copy of a
    parameter's block (a whole parameter on four chips, a Megatron half
    on the two chips of its ``dp`` pair) is bitwise the same, and the
    AdamW state of a split parameter is split as the parameter is. The
    ``dp`` ranks see different sequences, so a gradient that was not
    summed over ``dp`` before the update leaves copies that differ."""
    import jax

    differ, copies = [], 0
    for name, value in step.state["params"].items():
        seen: Dict[Any, np.ndarray] = {}
        for shard in value.addressable_shards:
            block = np.asarray(shard.data)
            first = seen.setdefault(str(shard.index), block)
            if first is not block:
                copies += 1
                if not np.array_equal(first, block):
                    differ.append(name)
                    break
    run.check(copies > 0 and not differ,
              f"{copies} copies of parameter blocks on other chips are "
              f"bitwise equal after the window (differ: {differ[:3]})")
    unsplit = []
    for name, slots in step.state["opt"]["slots"].items():
        spec = step.state["params"][name].sharding.spec
        for leaf in jax.tree.leaves(slots):
            if getattr(leaf, "ndim", 0) and leaf.sharding.spec != spec:
                unsplit.append(name)
                break
    run.check(not unsplit, "the optimizer state of every parameter is "
              f"split as the parameter is (not so: {unsplit[:3]})")


def build_step(run: Run, model, opt):
    """``ShardedTrainStep`` on the traffic mix's mesh: the batch over
    ``dp``, the Megatron rule over ``mp``. Building it spreads the
    model's arrays over the chips."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.models import pretraining_loss
    from paddle_tpu.parallel import (ShardedTrainStep, create_mesh,
                                     megatron_param_rule)

    mesh = create_mesh(dict(run.mix["mesh"]), devices=run.devices)
    log(f"mesh {dict(mesh.shape)} over {mesh.devices.ravel().tolist()}")
    step = ShardedTrainStep(
        model, opt, pretraining_loss, mesh, batch_spec=P("dp"),
        param_rule=megatron_param_rule(),
        seed=generator.small_seed(run.seed, "dropout"))
    spread = {name: len({s.device for s in v.addressable_shards})
              for name, v in step.state["params"].items()
              if v.sharding.spec != P()}
    run.check(bool(spread) and set(spread.values()) == {run.chips},
              f"{len(spread)} parameters are split over mp, each with "
              f"shards on {run.chips} devices")
    return step


def run(run: Run) -> Dict[str, Any]:
    import paddle_tpu as pt

    mix, cfg = run.mix, run.config["model"]
    pt.set_flags({"enable_metrics": True})
    batch = int(mix["batch_per_chip"]) * run.chips
    seq, every = int(mix["seq"]), int(mix["loss_fetch_every"])
    t0 = time.perf_counter()
    model, opt = train_step.build_model(run)
    batches = generator.pretraining_batches(mix, cfg["vocab_size"], batch,
                                            run.seed)
    log(f"model and {len(batches)} host batches of {batch} x {seq} "
        f"built in {time.perf_counter() - t0:.1f}s")
    step = build_step(run, model, opt)
    t0 = time.perf_counter()
    check_parity_on_mesh(run, step, model, batches[0])
    log(f"mesh parity check took {time.perf_counter() - t0:.1f}s")

    n_calls = 0

    def step_once():
        nonlocal n_calls
        ids, pos, mlm, nsp = batches[n_calls % len(batches)]
        n_calls += 1
        return step(ids, labels=(mlm, nsp), masked_positions=pos)

    observed = measure(run, step, step_once,
                       lambda m: {"loss": float(m["loss"])}, every,
                       batch * seq)
    check_replicas(run, step)
    flops = arithmetic.bert_flops_per_token(cfg, seq, int(mix["predicted"]))
    observed["counters"]["trace_model_flops"] = \
        flops * batch * seq * every
    if not run.rehearsal:
        kind = run.devices[0].device_kind
        tokens_per_s = observed["end_to_end"]["train_tokens_per_s"]
        log(f"train_tokens_per_s {tokens_per_s:.1f} over {run.chips} "
            f"chips; model_flops_per_token {flops:.0f}; MFU "
            f"{100 * arithmetic.mfu(tokens_per_s, flops, run.chips, kind):.2f}"
            f"% of {run.chips} x bf16 peak of {kind!r}")
    pt.set_flags({"enable_metrics": False})
    return observed
