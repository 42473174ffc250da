"""The comparison that decides ``correct`` for the hybrid language
model's cell, at tiny widths on the CPU: the system against the plain
reference, the faults that must turn ``correct`` false, and the
configuration file's two views of one model."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks import control_drill, harness, lm_generator, manifest as mf
from benchmarks.runners import lm_train_step
from paddle_tpu.nn.layers import moe
from test_bench_parity import make_run

CELL = "nemotron3_nano_ep16_s8k"
ROUTE = moe.DroplessMoE.route


def case(run):
    model = lm_train_step.build_model(run)
    batch = lm_generator.next_token_batches(
        run.mix, run.config["model"]["vocab_size"], 2, run.seed)[0]
    return model, batch


def test_loss_and_gradients_agree_with_the_reference():
    run = make_run(CELL)
    model, batch = case(run)
    lm_train_step.check_parity(run, model, batch)
    tol = run.config["tolerances"]
    assert run.margins["parity_loss_abs"] <= tol["loss_abs"]
    for leaf, limit in tol["grad_rel_l2"].items():
        assert run.margins["parity_grad_rel:" + leaf] <= limit, leaf
    assert model.training, "the timed path is compared: training mode"


def test_a_dropped_layer_fails_the_comparison():
    run = make_run(CELL)
    model, batch = case(run)
    model.layers[2].forward = lambda x: (x, None)
    with pytest.raises(harness.CheckFailed, match="parity"):
        lm_train_step.check_parity(run, model, batch)


@pytest.mark.parametrize("fault", ["softmax_router", "dropped_choice"])
def test_a_planted_routing_fault_fails_the_comparison(fault):
    run = make_run(CELL)
    model, batch = case(run)
    leaves = list(run.config["tolerances"]["grad_rel_l2"])
    with control_drill.LM[fault](run, model, leaves):
        with pytest.raises(harness.CheckFailed, match="parity"):
            lm_train_step.check_parity(run, model, batch)
    assert moe.DroplessMoE.route is ROUTE, "the fault is taken out again"


def test_the_controls_go_through_the_cells_comparison():
    """``control_drill``'s three controls through ``check_parity``:
    the planted faults end not correct at this size too; the reference
    one precision down reads every margin, and its bf16 loss lies
    several times further from the f32 one than the system's (at the
    cell's size, on the chip, that is over the limit on nine sequences
    in ten, so both sequences of a batch are compared: PERF.md)."""
    run = make_run(CELL)
    run.sweeping = True
    got = control_drill.lm_controls(run, list(control_drill.LM))
    assert not got["softmax_router"]["correct"]
    assert not got["dropped_choice"]["correct"]
    low = got["lower_precision"]["margins"]
    assert set(low) == {"parity_loss_abs"} | {
        "parity_grad_rel:" + k for k in run.config["tolerances"][
            "grad_rel_l2"]}
    model, batch = case(run)
    lm_train_step.fit_router_bias(run, model, batch[0])
    lm_train_step.check_parity(run, model, batch)
    assert low["parity_loss_abs"] > 3 * run.margins["parity_loss_abs"]


def test_a_second_trace_at_the_same_shapes_is_a_fresh_one():
    """Two comparisons of one model in one process (a control after
    another): ``jax.checkpoint`` must not hand the second trace what the
    first one closed over."""
    run = make_run(CELL)
    model, batch = case(run)
    lm_train_step.check_parity(run, model, batch)
    first = dict(run.margins)
    lm_train_step.check_parity(run, model, batch)
    assert run.margins == first


def test_the_configuration_file_states_one_model():
    """The top level holds the source's keys (the published value
    wherever ``reduced`` does not name the key), ``model`` what the
    program is built from: where both hold a key they agree, and the
    stated count is the model's."""
    cfg = mf.Manifest().config("nemotron3_nano_30b_a3b")
    for key, value in cfg["model"].items():
        if key in cfg:
            assert cfg[key] == value, key
    assert set(cfg["published"]) - {"note"} == set(cfg["reduced"])
    assert cfg["model"]["n_routed_experts_total"] \
        == cfg["published"]["n_routed_experts"]
    assert cfg["num_hidden_layers"] == len(
        cfg["published"]["hybrid_override_pattern"])
    from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM
    shapes = jax.eval_shape(lambda: NemotronHForCausalLM(
        NemotronHConfig(**cfg["model"])).param_dict())
    count = sum(int(jnp.prod(jnp.asarray(v.shape)))
                for v in shapes.values())
    assert count == cfg["parameters"]["count"] == 666_962_944
    assert cfg["parameters"]["bytes_at_16_a_parameter"] == 16 * count
