"""``readers.kernels.kernel_peak_pct_per_event`` on the hand-built trace
of ``test_bench_program_readers``: a kernel's share of the peak where a
step runs a noted call site more than once (a recomputed layer)."""

import pytest

from benchmarks import manifest as mf
from benchmarks.readers import kernels, program
from test_bench_program_readers import PEAK, observed  # noqa: F401

M = mf.Manifest()
ARGS = M.metric_file("train.flash_fwd_roofline")["args"]


def test_the_metric_file_names_the_reader_and_the_forward_kernel():
    spec = M.metric_file("train.flash_fwd_roofline")
    assert mf.resolve(spec["reader"]) is kernels.kernel_peak_pct_per_event
    accepted = M.metric_file("train.flash_fwd_mxu_pct")["args"]
    assert ARGS == accepted, "same kernel, entry point and peak"


@pytest.mark.parametrize("steps_counted, times_a_step", [(2, 1), (1, 2)])
def test_every_event_does_its_sites_flops(observed, steps_counted,  # noqa: F811
                                          times_a_step):
    """Two flash_fwd events of 100 ns in the slice, one noted site of
    4e6 FLOPs: as one event a step over two steps, and as a site run
    twice in one step."""
    obs = observed()
    obs["counters"]["trace_steps"] = steps_counted
    want = 100.0 * 4.0e6 * steps_counted * times_a_step / 200e-9 / PEAK
    assert kernels.kernel_peak_pct_per_event(obs, **ARGS) \
        == pytest.approx(want)
    once = program.kernel_peak_pct(obs, **ARGS)
    if times_a_step == 1:
        assert once == pytest.approx(want), "the accepted reader agrees"
    else:
        assert once is None, "which is why this reader exists"


def test_a_site_that_ran_in_some_steps_only_reads_nothing(observed):  # noqa: F811
    assert kernels.kernel_peak_pct_per_event(
        observed(drop_last_flash=True), **ARGS) is None


def test_no_noted_site_reads_nothing(observed):  # noqa: F811
    args = dict(ARGS, kernels=["no_such_kernel"])
    assert kernels.kernel_peak_pct_per_event(observed(), **args) is None
