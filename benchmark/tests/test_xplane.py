"""The reduction from a trace to numbers: first on a timeline small
enough to do by hand, then on event extracts recorded on the v5e in
PR 22 (``data/PROVENANCE.txt``), whose expected values were computed
apart from the code under test, on a timeline rasterised at 10 ns."""

import os

import pytest

from benchmark import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6  # nanoseconds


def test_an_op_is_named_by_instruction_opcode_and_shape():
    text = ("%psum.50 = f32[102760448]{0:T(1024)} all-reduce(f32[102760448]"
            "{0:T(1024)} %bitcast.11), channel_id=1, replica_groups={{0,1}}")
    assert xplane.short_name(text) == (
        "psum.50 all-reduce f32[102760448]", True)
    text = ("%fusion.230 = (bf16[64]{0:T(256)(128)(2,1)S(1)}, bf16[64,224,"
            "224,64]{3,0,2,1:T(8,128)(2,1)}) fusion(f32[3,3,64,64]{3,2,1,0} "
            "%all-reduce.1), kind=kOutput, calls=%fused_computation.370")
    # an operand called all-reduce does not make a fusion a collective
    assert xplane.short_name(text) == (
        "fusion.230 fusion (bf16[64], bf16[64,224,224,64])", False)
    assert xplane.short_name("%all-gather-start.3 = (f32[8]{0}, f32[32]{0}) "
                             "all-gather-start(%x)")[1]
    assert xplane.short_name("not an instruction") == (
        "not an instruction", False)


def test_a_conditional_is_not_counted_beside_its_children():
    events = [("cond", 0, 100), ("child.a", 10, 30), ("child.b", 50, 40),
              ("after", 100, 20)]
    assert [e[0] for e in xplane.innermost(events)] == [
        "child.a", "child.b", "after"]


def _by_hand(starts=(0, 100, 200, 300, 700, 800)):
    """One chip, runs of a 60 ms step program starting every 100 ms,
    but the fourth period stretched to 400 ms.  In each step: a 40 ms
    fusion, then a 15 ms all-reduce, of which an asynchronous copy
    hides nothing and a 5 ms fusion running beside the in-flight
    all-gather hides 5 ms."""
    modules, ops, in_flight = [], [], []
    for t in starts:
        modules.append(["jit_step(1)", t * MS, 60 * MS])
        ops += [["fusion.1 fusion f32[8]", t * MS, 40 * MS],
                ["psum.2 all-reduce f32[8]", (t + 40) * MS, 15 * MS],
                ["fusion.3 fusion f32[8]", (t + 55) * MS, 5 * MS]]
        in_flight.append(
            ["all-gather-start.4 all-gather-start f32[8]", (t + 50) * MS,
             10 * MS])
    modules.append(["jit_other(2)", (starts[-1] + 100) * MS, 1 * MS])
    host = [["bench:fence_wait", 100 * MS, 95 * MS],
            ["bench:data_next", 195 * MS, 5 * MS],
            ["bench:dispatch", 200 * MS, 3 * MS],
            ["bench:fence_wait", 203 * MS, 95 * MS]]
    return {"device": {"/device:TPU:0": {
                xplane.MODULES_LINE: modules, xplane.OPS_LINE: ops,
                xplane.ASYNC_LINE: in_flight}},
            "host": host,
            "collectives": ["psum.2 all-reduce f32[8]",
                            "all-gather-start.4 all-gather-start f32[8]"]}


def test_a_timeline_done_by_hand():
    r = xplane.reduce(_by_hand())
    (chip,) = r.devices
    # candidates are the periods that start at 100, 200, 300 and 700,
    # of 100, 100, 400 and 100 ms: their lower quartile is 100 ms, and
    # the one from 300 to 700 is over 110
    assert (len(chip.step_ns), chip.dropped) == (3, 1)
    assert chip.periods == [(100 * MS, 300 * MS), (700 * MS, 800 * MS)]
    assert chip.busy_in_step_ns == [60 * MS] * 3
    # an op ran in 180 of the 300 ms kept: all three from the trace
    assert (r.busy_s, r.window_s) == (
        pytest.approx(0.180), pytest.approx(0.300))
    assert r.idle_share == pytest.approx(0.4)
    assert r.busy_ms_per_step == pytest.approx(60.0)
    assert r.device_step_ms == pytest.approx(60.0)
    # 40..55 the all-reduce, 50..60 the all-gather in flight: 20 ms
    assert r.collective_ms_per_step == pytest.approx(20.0)
    # less the 5 ms during which fusion.3 ran beside the all-gather
    assert r.exposed_collective_ms_per_step == pytest.approx(15.0)
    assert r.top_ops(2) == [["fusion.1 fusion f32[8]", pytest.approx(0.12)],
                            ["psum.2 all-reduce f32[8]",
                             pytest.approx(0.045)]]
    # the gaps are 160..200, 260..300 and 760..800; the host sat in the
    # fence during the first two, nothing of ours covers the third
    assert r.longest_gaps(3) == [["fence_wait", pytest.approx(0.040)],
                                 ["fence_wait", pytest.approx(0.040)],
                                 ["other", pytest.approx(0.040)]]


def test_a_job_the_host_paces_keeps_its_periods():
    """Every 200 ms a 60 ms step: no period stands out, all are kept,
    and the chip is idle 70 % of the time."""
    r = xplane.reduce(_by_hand(starts=range(0, 1600, 200)))
    (chip,) = r.devices
    assert (len(chip.step_ns), chip.dropped) == (6, 0)
    assert r.idle_share == pytest.approx(0.7)
    assert r.window_s == pytest.approx(1.2)


def test_too_little_left_is_no_reduction():
    # four runs leave two whole periods between the first and the last
    assert xplane.reduce(_by_hand(starts=(0, 100, 200, 300))) is None
    assert xplane.reduce(
        {"device": {}, "host": [], "collectives": []}) is None  # the CPU


# name, then per chip: periods kept, dropped, ms an op ran in the kept
# periods, their summed length in ms, collective and exposed collective
# ms a step; then the idle share of all chips together
RECORDED = [
    ("vgg16-b64-dp4.12steps", [
        (4, 6, 293.63584, 411.42376, 9.343603, 9.343603),
        (3, 7, 226.03433, 283.41497, 9.673210, 9.673210),
        (3, 7, 222.68405, 261.08512, 9.675527, 9.675527),
        (3, 7, 224.59162, 281.25137, 9.679860, 9.679860)], 0.2184245),
    ("resnet50-b256-dp1.12steps", [
        (6, 4, 577.41378, 590.64090, 0.0, 0.0)], 0.0223945),
]


@pytest.mark.parametrize("name, chips, idle", RECORDED)
def test_traces_recorded_on_the_chip(name, chips, idle):
    ex = xplane.load_extract(os.path.join(DATA, name + ".json.gz"))
    r = xplane.reduce(ex)
    assert len(r.devices) == len(chips)
    for d, (kept, dropped, busy, window, coll, exposed) in zip(
            r.devices, chips):
        n = len(d.step_ns)
        assert (n, d.dropped) == (kept, dropped)
        assert d.busy_ns / MS == pytest.approx(busy, abs=5e-3)
        assert d.window_ns / MS == pytest.approx(window, abs=1e-3)
        assert d.collective_ns / n / MS == pytest.approx(coll, abs=1e-4)
        assert d.exposed_collective_ns / n / MS == pytest.approx(
            exposed, abs=1e-4)
    # what the result line carries, and what the driver makes of it
    from statistics import mean, median
    assert r.busy_s == pytest.approx(
        mean(c[2] for c in chips) / 1e3, abs=5e-6)
    assert r.window_s == pytest.approx(
        mean(c[3] for c in chips) / 1e3, abs=1e-6)
    assert r.idle_share == pytest.approx(idle, abs=1e-5)
    assert 1 - r.busy_s / r.window_s == r.idle_share
    # the metrics of the exchange: the median over the chips
    assert r.collective_ms_per_step == pytest.approx(
        median(c[4] for c in chips), abs=1e-4)
    assert r.exposed_collective_ms_per_step == pytest.approx(
        median(c[5] for c in chips), abs=1e-4)


def test_the_four_chip_trace_shows_the_exchange():
    ex = xplane.load_extract(
        os.path.join(DATA, "vgg16-b64-dp4.12steps.json.gz"))
    assert ex["collectives"] == [
        "all-reduce all-reduce (f32[4096], f32[4096], f32[16777216], "
        "f32[2359296], f32[])",
        "psum.50 all-reduce f32[102760448]",
        "psum.53 all-reduce f32[16452392]"]
    r = xplane.reduce(ex)
    # the 411 MB bucket is the op with most time on the chip
    assert r.top_ops(1)[0][0] == "psum.50 all-reduce f32[102760448]"
