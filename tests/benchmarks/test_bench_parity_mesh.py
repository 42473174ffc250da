"""The comparison that decides ``correct`` for the four-chip cell, at
tiny widths on four virtual CPU devices: the model as
``ShardedTrainStep`` spread it over the dp2 x mp2 mesh against the plain
reference on one device, and faults that exist only between chips (an
exchange made twice, a reduction over ``dp`` left out, copies of a
parameter that drifted apart) that must turn ``correct`` false. None of
them is visible to the one-device comparison of ``bert_base_s512``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmarks import control_drill, generator, harness
from benchmarks.runners import sharded_train_step as runner, train_step
from test_bench_parity import make_run

CELL = "bert_base_s512_dp2mp2"


def mesh_case():
    run = make_run(CELL)
    if len(jax.devices()) < run.chips:
        pytest.skip(f"needs {run.chips} (virtual) devices")
    model, opt = train_step.build_model(run)
    batch = generator.pretraining_batches(
        run.mix, run.config["model"]["vocab_size"],
        int(run.mix["batch_per_chip"]) * run.chips, run.seed)[0]
    return run, model, runner.build_step(run, model, opt), batch


def test_the_sharded_model_agrees_with_the_reference():
    run, model, step, batch = mesh_case()
    runner.check_parity_on_mesh(run, step, model, batch)
    tol = run.config["tolerances"]
    assert run.margins["parity_loss_abs"] <= tol["loss_abs"]
    assert run.margins["parity_grad_rel"] <= tol["grad_rel_l2"]
    assert model.training, "the model is back in training mode"
    # the compared arrays are the step's own, split as the rule says
    specs = {n: v.sharding.spec for n, v in step.state["params"].items()}
    assert specs["bert.encoder.layers.0.self_attn.q_proj.weight"] \
        == P(None, "mp")
    assert specs["bert.encoder.layers.1.linear2.weight"] == P("mp", None)


@pytest.mark.parametrize("fault", sorted(control_drill.MESH))
def test_a_fault_between_the_chips_fails_the_comparison(fault):
    run, model, step, batch = mesh_case()
    control_drill.MESH[fault](step, model)
    with pytest.raises(harness.CheckFailed, match="mesh parity"):
        runner.check_parity_on_mesh(run, step, model, batch)


def test_copies_that_drifted_apart_fail_the_state_check():
    run, model, step, batch = mesh_case()
    ids, pos, mlm, nsp = batch
    step(ids, labels=(mlm, nsp), masked_positions=pos)
    runner.check_replicas(run, step)        # what a sound step leaves
    name = "cls.transform.weight"
    value = step.state["params"][name]
    blocks = [s.data for s in value.addressable_shards]
    blocks[-1] = blocks[-1] + jnp.asarray(0.01, blocks[-1].dtype)
    step.state["params"][name] = jax.make_array_from_single_device_arrays(
        value.shape, value.sharding, blocks)
    with pytest.raises(harness.CheckFailed, match="bitwise equal"):
        runner.check_replicas(run, step)
    assert np.isfinite(np.asarray(blocks[-1], np.float32)).all()
