"""bench/trace.py on a small recorded chip trace.

``bench/testdata/tiny_r14.xplane.pb`` was recorded on one TPU v5e
(``TPU v5 lite``) by ``--trace 1`` of the r14-k256-n2 cell at a test size
(two stages of 16/32 channels, 16x16 images, 8 clients a round, one round
a segment): three segments. It was pruned to the device plane and the
benchmark's host spans, with each op's HLO text cut to its name, so that
it stays small. The expected numbers below were read from the full
recording; the busy time is checked again by an independent sweep.
"""
import os

import pytest

from bench import trace

PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "testdata", "tiny_r14.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace.Reduction(trace.load(PATH))


def test_busy_window_and_idle(red):
    assert red.window_s == pytest.approx(0.060889169, abs=1e-9)
    assert red.busy_s == pytest.approx(0.000971148, abs=1e-9)
    assert list(red.devices) == ["/device:TPU:0"]
    idle = red.idle_by_span()
    assert set(idle) == {"bench.segment"}
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s,
                                               abs=1e-9)


def test_busy_by_independent_sweep(red):
    """Busy time as the length covered by at least one leaf op, counted
    by a sweep over the sorted interval end points."""
    lo, hi = red.window
    points = []
    for s, e, _ in red.devices["/device:TPU:0"][0]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort(key=lambda p: (p[0], -p[1]))
    depth, covered, last = 0, 0, None
    for t, d in points:
        if depth > 0:
            covered += t - last
        depth += d
        last = t
    assert covered / 1e9 == pytest.approx(red.busy_s, abs=1e-12)


def test_containers_are_left_out(red):
    names = {trace.category(n) for _, _, n in red.devices[
        "/device:TPU:0"][0]}
    assert not names & set(trace.CONTAINERS)
    assert "fusion" in names and "cco_stats_pallas" in names


def test_categories_kernel_and_boundaries(red):
    by_cat = red.time_by(trace.category)
    assert by_cat["fusion"] == pytest.approx(0.000324209, abs=1e-9)
    assert by_cat["multiply_reduce_fusion"] == pytest.approx(0.000286446,
                                                             abs=1e-9)
    assert by_cat["copy"] == pytest.approx(0.000201571, abs=1e-9)
    kernel = lambda n: n.startswith("cco_stats_pallas")  # noqa: E731
    assert red.op_calls(kernel) == 3
    assert red.op_seconds(kernel) == pytest.approx(7.35e-07, abs=1e-12)
    assert red.main_module().startswith("jit__unknown")
    gaps = red.boundary_idle_s()
    assert gaps == pytest.approx([0.019926981, 0.019066245], abs=1e-9)
    top = red.breakdown()
    assert top["device_ops"][0] == ["copy.1", pytest.approx(0.000115098,
                                                            abs=1e-9)]
    assert len(top["device_ops"]) == 10 and len(top["idle_gaps"]) <= 10


HLO = """\
HloModule jit_step

%fused_computation.1 (param_0: f32[8,16,16,3], param_1: f32[3,3,3,16]) -> f32[8,16,16,16] {
  %param_0 = f32[8,16,16,3]{3,2,1,0} parameter(0)
  %param_1 = f32[3,3,3,16]{3,2,1,0} parameter(1)
  ROOT %convolution.3 = f32[8,16,16,16]{3,2,1,0} convolution(%param_0, %param_1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
}

%fused_computation.2 (param_0.1: f32[8,16]) -> f32[8] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  ROOT %reduce.1 = f32[8]{0} reduce(%param_0.1, %c), dimensions={1}, to_apply=%add
}

ENTRY %main.9 (Arg_0.1: f32[8,16,16,3], Arg_1.2: f32[3,3,3,16]) -> f32[8] {
  %fusion.7 = f32[8,16,16,16]{3,2,1,0} fusion(%Arg_0.1, %Arg_1.2), kind=kOutput, calls=%fused_computation.1
  %convolution.9 = f32[8,16,16,16]{3,2,1,0} convolution(%fusion.7, %Arg_1.2), window={size=3x3}
  ROOT %fusion.8 = f32[8]{0} fusion(%convolution.9), kind=kLoop, calls=%fused_computation.2
}
"""


def test_conv_ops_from_hlo():
    assert trace.conv_ops(HLO) == {"fusion.7", "convolution.9",
                                   "convolution.3"}
