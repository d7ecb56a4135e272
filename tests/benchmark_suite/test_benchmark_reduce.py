"""The reduction from a trace to numbers, on a small trace recorded on a
TPU v5e (three runs of one tanh(x @ w) program with the benchmark's host
annotations around them; ``.chipwork`` probe of PR 23), and the arithmetic
over readings."""

import os

import pytest

from benchmark import harness
from benchmark import reduce as R
from benchmark import serve_cell

TRACE = os.path.join(harness.HERE, "testdata", "probe_v5e.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return R.reduce_file(TRACE)


def test_busy_and_idle_share_of_the_recorded_trace(red):
    assert red.chips == 1
    assert red.window_s == pytest.approx(0.134084125, rel=1e-9)
    assert red.busy_s == pytest.approx(1.4617e-05, rel=1e-9)
    assert red.idle_share == pytest.approx(0.99989098635, rel=1e-9)


def test_per_operation_times(red):
    ops = {R.short_name(k): v for k, v in red.op_seconds().items()}
    assert ops["convolution_tanh_fusion bf16[512,512]"] == \
        pytest.approx(1.0607e-05, rel=1e-9)
    assert ops["copy-done bf16[512,512]"] == pytest.approx(2.993e-06, rel=1e-9)
    assert red.count_matching("fusion") == 3
    assert red.seconds_matching("tanh") == pytest.approx(1.0607e-05, rel=1e-9)
    runs = red.module_runs("jit_step")
    assert [round(s * 1e9) for _, s in runs] == [3457, 4718, 4752]
    top = red.breakdown(3)["device_ops"]
    assert top[0][0] == "convolution_tanh_fusion bf16[512,512]"
    assert len(top) == 3


def test_idle_gaps_are_attributed_to_what_the_host_was_doing(red):
    gaps = red.idle_gaps(4)
    # the host sat in the benchmark's fetch while the device was idle
    assert gaps[0] == ["bench.fetch_loss", pytest.approx(0.069571677)]
    assert gaps[1][0] == "bench.fetch_loss"
    assert [g[0] for g in gaps].count("bench.make_batch") == 1
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))
    assert red.collective_exposed_s() == 0.0


def test_union_and_cover_arithmetic():
    merged = R._union([(0, 10), (5, 20), (30, 40)])
    assert merged == [[0, 20], [30, 40]]
    assert R._covered(merged, 15, 35) == 10
    # a collective that compute hides, one that nothing hides
    devs = {0: [("%all-reduce.1 = f32[8]", 0, 10), ("%fusion.1 = f32[8]", 0, 10),
                ("%all-gather.2 = f32[8]", 20, 10)]}
    red = R.Reduction(devs, {}, [])
    assert red.collective_exposed_s() == pytest.approx(10e-9)
    assert red.busy_s == pytest.approx(20e-9)
    assert red.window_s == pytest.approx(30e-9)
    # a loop's own event holds its body's: it is not counted twice
    loop = {0: [("%while.3 = (s32[]) while(...)", 0, 100),
                ("%fusion.9 = f32[4]{0} fusion(...)", 10, 50)]}
    assert list(R.Reduction(loop, {}, []).op_seconds()) == \
        ["%fusion.9 = f32[4]{0} fusion(...)"]
    assert R.short_name("%fusion.9 = bf16[4,8]{1,0:T(8,128)} fusion(%x)") \
        == "fusion.9 bf16[4,8]"


def test_one_slow_reading_moves_the_rate_and_not_the_median_reading():
    """The end-to-end rates are all the work over all the window's time:
    a stall costs them in full. The median reading, a per-layer metric
    beside them, does not move."""
    tokens = [100] * 21
    steady = [1.0] * 21
    stalled = [1.0] * 20 + [10.0]           # one reading ten times slow
    assert harness.whole_window_rate(tokens, steady) == pytest.approx(100.0)
    assert harness.whole_window_rate(tokens, stalled) == pytest.approx(70.0)
    assert harness.rate_from_readings(tokens, stalled) == \
        harness.rate_from_readings(tokens, steady) == 100.0
    assert harness.agree([1.0, 1.001, 0.999, 1.0, 1.0], 0.005)
    assert not harness.agree([1.0, 1.0, 1.0, 1.0, 1.1], 0.005)


def test_a_stalled_delivery_moves_tpot_p95_and_not_tpot_median():
    def stream(gaps):
        t, ev = 0.0, []
        for k, g in enumerate(gaps):
            t += g
            ev.append((t, 10 + 8 * k, [1] * 8))
        return {"events": ev}

    steady = [stream([0.2] * 41) for _ in range(4)]
    stalled = [stream([0.2] * 30 + [2.0] + [0.2] * 10) for _ in range(4)]
    a = serve_cell.tpot_samples(steady, 0.0, 100.0)
    b = serve_cell.tpot_samples(stalled, 0.0, 100.0)
    assert len(a) == len(b) == 4 * 40
    assert harness.median(a) == pytest.approx(25.0)
    assert harness.median(b) == pytest.approx(25.0)
    assert harness.percentile(a, 95) == pytest.approx(25.0)
    four = [stream([0.2] * 10 + [2.0] * 4 + [0.2] * 27) for _ in range(4)]
    c = serve_cell.tpot_samples(four, 0.0, 100.0)
    assert harness.median(c) == pytest.approx(25.0)
    assert harness.percentile(c, 95) == pytest.approx(250.0)
    # the stall costs images_per_s (every token from the first harvest to
    # the last, over that time) in full; the median harvest does not move
    delivs = serve_cell.deliveries(stalled, 0.0, 100.0)
    units, secs = serve_cell.harvest_readings(delivs, 0.04)
    assert harness.whole_window_rate(units, secs) == pytest.approx(
        40 * 32 / (39 * 0.2 + 2.0))
    assert harness.rate_from_readings(units, secs) == pytest.approx(160.0)
    assert max(secs) == pytest.approx(2.0)


def test_percentile_is_linear_between_ranks():
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    assert harness.percentile([0, 10], 95) == pytest.approx(9.5)
    with pytest.raises(ValueError):
        harness.percentile([], 95)
