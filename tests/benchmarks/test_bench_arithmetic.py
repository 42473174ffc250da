"""Metric arithmetic and the traffic generator."""

import numpy as np
import pytest

from benchmarks import arithmetic, generator, manifest as mf

M = mf.Manifest()


def test_spread_is_iqr_over_median_with_statistics_quartiles():
    # statistics.quantiles(n=4) of 1..6 gives 1.75 and 5.25
    assert arithmetic.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


def test_bert_base_flops_per_token_against_a_hand_value():
    cfg = M.config("bert_base")["model"]
    # encoder: 12 x (4 x 768^2 + 2 x 768 x 3072) = 84,934,656 params
    # MLM transform + tied decoder on 80 of 512 positions:
    #   (589,824 + 23,440,896) x 80 / 512 = 3,754,800
    # pooler + NSP on 1 of 512: (589,824 + 1,536) / 512 = 1,155
    # x 6, plus attention 12 x 12 x 512 x 768 = 56,623,104
    want = 6 * (84_934_656 + 3_754_800 + 1_155) + 56_623_104
    assert want == 588_766_770
    got = arithmetic.bert_flops_per_token(cfg, seq=512, predicted=80)
    assert got == pytest.approx(want, rel=1e-9)
    # the MLM head on all 512 positions would add a sixth
    full = arithmetic.bert_flops_per_token(cfg, seq=512, predicted=512)
    assert 1.15 < full / got < 1.25


def test_peaks_table_and_mfu():
    v5e = arithmetic.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        arithmetic.peaks_for("cpu")
    with pytest.raises(KeyError):
        arithmetic.peaks_for("_source")
    assert arithmetic.mfu(150_000, 588_766_770, 1, "TPU v5 lite") \
        == pytest.approx(0.4483, abs=1e-4)
    assert arithmetic.mfu(600_000, 588_766_770, 4, "TPU v5 lite") \
        == pytest.approx(0.4483, abs=1e-4)


TRAINING_MIXES = [w["traffic"] for w in M.doc["workloads"]
                  if "seq" in M.traffic(w["traffic"])]


@pytest.mark.parametrize("name", TRAINING_MIXES)
@pytest.mark.parametrize("seed", [3, 2 ** 63 - 1])
def test_pretraining_batches(name, seed):
    mix = dict(M.traffic(name), pool_batches=2, seq=64, predicted=10)
    a = generator.pretraining_batches(mix, 30522, 4, seed)
    b = generator.pretraining_batches(mix, 30522, 4, seed)
    assert len(a) == 2
    for (ids, pos, labels, nsp), other in zip(a, b):
        assert all(np.array_equal(x, y)
                   for x, y in zip((ids, pos, labels, nsp), other))
        assert ids.shape == (4, 64) and ids.dtype == np.int32
        assert pos.shape == (4, 10) and pos.dtype == np.int32
        assert labels.shape == (4, 10) and labels.dtype == np.int64
        assert nsp.shape == (4,) and set(nsp) <= {0, 1}
        assert (np.diff(pos, axis=1) > 0).all(), "sorted and distinct"
        assert 0 <= ids.min() and ids.max() < 30522
        assert 0 <= labels.min() and labels.max() < 30522
    ids, pos, labels, _ = a[0]
    at_pos = np.take_along_axis(ids, pos, axis=1)
    assert (at_pos == mix["mask_id"]).mean() > 0.5
    c = generator.pretraining_batches(mix, 30522, 4, seed - 1)
    assert not np.array_equal(a[0][0], c[0][0])


def test_seeds_fold_to_what_the_program_accepts():
    for seed in (0, 2 ** 31, 2 ** 32 + 1, 2 ** 63 - 1, -5):
        assert 0 <= generator.small_seed(seed, "weights") < 2 ** 31
    assert generator.small_seed(1, "weights") \
        != generator.small_seed(1, "dropout")
    assert generator.small_seed(2 ** 32 + 1, "weights") \
        != generator.small_seed(1, "weights")


class FakeChip:
    def __init__(self, id, readings):
        self.id, self.readings = id, iter(readings)

    def memory_stats(self):
        return next(self.readings)


def test_memory_is_what_one_reading_saw_held_never_a_sum_of_two_peaks():
    import time

    from benchmarks import harness
    run = harness.Run(harness.parse_args(
        ["--workload", "bert_base_s512", "--rehearsal"]),
        time.perf_counter())
    gb = 10 ** 9
    run.devices = [
        FakeChip(0, [   # the parity programs made the in-use peak early
            {"bytes_in_use": 2 * gb, "bytes_reserved": 1 * gb},
            {"bytes_in_use": 1 * gb, "bytes_reserved": 6 * gb},
            {"peak_bytes_in_use": 2 * gb, "peak_bytes_reserved": 6 * gb}]),
        FakeChip(1, [None, None, None])]    # a backend that says nothing
    run.window_starts()
    assert harness.memory_peak_bytes(run) == 7 * gb     # not 2 + 6
    run.devices = [FakeChip(0, [{"bytes_in_use": gb}, {"peak_bytes_in_use":
                                                       3 * gb}])]
    run.held_bytes.clear()
    assert harness.memory_peak_bytes(run) == 3 * gb
