"""The comparison that decides ``correct``, at tiny widths on the CPU:
the system against the plain reference, and faults that must turn
``correct`` false."""

import time

import jax
import pytest

from benchmarks import generator, harness
from benchmarks.runners import train_step


def make_run(workload: str, seed: int = 5) -> harness.Run:
    ns = harness.parse_args(["--workload", workload, "--seed", str(seed),
                             "--seconds", "1", "--rehearsal"])
    run = harness.Run(ns, time.perf_counter())
    run.devices = jax.devices()[:run.chips]
    return run


# -- training -----------------------------------------------------------------

def bert_case(run):
    model, opt = train_step.build_model(run)
    step = train_step.make_step(run, model, opt)
    batch = generator.pretraining_batches(
        run.mix, run.config["model"]["vocab_size"], 2, run.seed)[0]
    return model, step, batch


def test_bert_loss_and_gradients_agree_with_the_reference():
    run = make_run("bert_base_s512")
    model, step, batch = bert_case(run)
    train_step.check_parity(run, step, model, batch)
    assert run.margins["parity_loss_abs"] <= 0.05
    assert run.margins["parity_grad_rel"] <= 0.1
    assert model.training, "the model is back in training mode"


def test_bert_a_dropped_layer_fails_the_comparison():
    run = make_run("bert_base_s512")
    model, step, batch = bert_case(run)
    dropped = model.bert.encoder.layers[1]
    dropped.forward = lambda src, src_mask=None: src
    with pytest.raises(harness.CheckFailed, match="parity"):
        train_step.check_parity(run, step, model, batch)


def test_bert_a_wrong_predicted_position_fails_the_comparison():
    run = make_run("bert_base_s512")
    model, step, batch = bert_case(run)
    ids, pos, labels, nsp = batch
    forward = model.forward

    def shifted(input_ids, masked_positions=None, **kw):
        return forward(input_ids,
                       masked_positions=(masked_positions + 1) % 32, **kw)

    model.forward = shifted
    with pytest.raises(harness.CheckFailed, match="parity"):
        train_step.check_parity(run, step, model, batch)


def test_another_seed_compiles_nothing_new():
    """What differs from run to run is an argument of the comparison's
    programs, never a constant inside them: a constant is part of the
    persistent cache's key, so every run would compile afresh and leave
    an entry of the parameters' size behind (the disk fills on run
    seven, not on run one)."""
    from paddle_tpu.sysconfig import compile_cache_stats

    def once(seed):
        run = make_run("bert_base_s512", seed=seed)
        model, step, batch = bert_case(run)
        train_step.check_parity(run, step, model, batch)

    once(11)
    before = compile_cache_stats()["misses"]
    once(2 ** 33 + 12)
    assert compile_cache_stats()["misses"] == before
