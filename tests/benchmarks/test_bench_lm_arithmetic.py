"""The hybrid model's FLOPs from shapes, and the two readers this
benchmark adds (a program counter, the whole step's share of the
peak)."""

import pytest

from benchmarks import lm_arithmetic, manifest as mf
from benchmarks.readers import counters, mfu

CFG = mf.Manifest().config("nemotron3_nano_30b_a3b")["model"]


def test_flops_by_hand_for_one_layer_of_each_kind():
    cfg = dict(CFG, hybrid_override_pattern="", vocab_size=0)
    tokens = 2 * 8192

    def only(pattern, pairs=0.0):
        return lm_arithmetic.nemotron_h_flops_per_step(
            dict(cfg, hybrid_override_pattern=pattern), 2, 8192, pairs) \
            / (6.0 * tokens)

    # M: projections 2688 x (4096 + 6144 + 64) and 4096 x 2688, then the
    # scan: C B^T 8 x 128 x 128, its product with x 64 x 128 x 64, the
    # chunk states and their read-out 64 x 64 x 128 each
    assert only("M") == 2688 * 10304 + 4096 * 2688 + 8 * 128 * 128 \
        + 64 * 128 * 64 + 2 * 64 * 64 * 128
    # E without a held pair: router and shared expert
    assert only("E") == 2688 * 128 + 2 * 2688 * 3712
    # a held pair: one expert's two matmuls
    assert only("E", pairs=tokens) - only("E") == 2 * 2688 * 1856
    # *: q and o 2688 x 4096, k and v 2688 x 256, causal QK^T and PV
    assert only("*") == 2 * 2688 * 4096 + 2 * 2688 * 256 \
        + 2 * 32 * 128 * 8192 / 2
    head = lm_arithmetic.nemotron_h_flops_per_step(
        dict(cfg, vocab_size=16384), 2, 8192, 0.0)
    assert head == 6.0 * tokens * 2688 * 16384


def test_the_cell_s_step_is_about_35_tflop():
    balanced = 2 * 8192 * 6 * 8 / 128 * 4
    flops = lm_arithmetic.nemotron_h_flops_per_step(CFG, 2, 8192, balanced)
    assert 34e12 < flops < 37e12


def test_counter_reader():
    assert counters.value({"counters": {"a": 2.5}}, "a") == 2.5
    assert counters.value({"counters": {}}, "a") is None
    assert counters.value({}, "a") is None


@pytest.mark.parametrize("observed", [
    {}, {"counters": {"trace_model_flops": 1e12, "chips": 1}},
    {"counters": {"chips": 1}, "trace_summary": {"window_s": 1.0}}])
def test_mfu_reader_reads_nothing_without_its_inputs(observed):
    assert mfu.mfu_pct(observed, "trace_model_flops") is None


def test_mfu_reader(monkeypatch):
    import jax

    class Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    observed = {"counters": {"trace_model_flops": 197e12, "chips": 4},
                "trace_summary": {"window_s": 0.5}}
    assert mfu.mfu_pct(observed, "trace_model_flops") \
        == pytest.approx(50.0)
