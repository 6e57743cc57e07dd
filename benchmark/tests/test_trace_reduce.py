"""trace_reduce against data/tiny_trace.textproto, a hand-made trace small
enough to compute every number by hand (microseconds below)."""
import os

import pytest

from benchmark.harness import trace_reduce as tr

US = 1e-6
HERE = os.path.dirname(os.path.abspath(__file__))
SESSION_START_NS = 1_790_000_000_000_000_000
# what the host was doing, as the loop's Spans recorded it on the wall clock
HOST = [("window", 50, 1100), ("loader", 500, 60), ("executor.submit", 560, 130),
        ("fetch_wait", 1100, 50), ("loader", 5000, 10)]


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    from benchmark.harness.spans import Spans
    with open(os.path.join(HERE, "data", "tiny_trace.textproto")) as f:
        profile = ProfileData.from_text_proto(f.read())
    loaded = tr.load(profile)
    assert loaded["session_start_ns"] == SESSION_START_NS
    spans = Spans()
    spans.records = [(name, SESSION_START_NS + start * 1000, dur * US)
                     for name, start, dur in HOST]
    return tr.reduce(loaded, spans.on_timeline(loaded["session_start_ns"]))


def test_window_is_the_window_span(reduced):
    assert reduced["window_s"] == pytest.approx(1100 * US)


def test_busy_is_the_union_of_op_intervals(reduced):
    d0 = reduced["devices"]["/device:TPU:0"]
    d1 = reduced["devices"]["/device:TPU:1"]
    # [100,400) + [420,500) + [700,1000): the while's children add nothing,
    # and neither does the asynchronous copy beside the operations
    assert d0["busy_s"] == pytest.approx(680 * US)
    assert d1["busy_s"] == pytest.approx(500 * US)
    assert reduced["busy_s"] == pytest.approx(590 * US)      # mean
    assert reduced["busy_s_min"] == pytest.approx(500 * US)  # the idlest


def test_operations_are_counted_by_class_at_their_self_time(reduced):
    ops = reduced["devices"]["/device:TPU:0"]["ops"]
    assert ops == pytest.approx({
        "while": 40 * US,                    # 200 - 80 - 80: its children's
        "fusion(kOutput)": 200 * US,         # %fusion.1, twice
        "multiply_add_fusion": 180 * US,     # %multiply_add_fusion.2, twice
        "all-reduce": 180 * US,
        "dropout": 80 * US,                  # the Mosaic kernel's own name
    })
    assert tr.op_class("%copy-done.166 = f32[8]{0} copy-done(%x)") \
        == "copy-done"


def test_collectives_by_opcode_and_mosaic_by_call_target(reduced):
    d0 = reduced["devices"]["/device:TPU:0"]
    d1 = reduced["devices"]["/device:TPU:1"]
    assert d0["collective_s"] == pytest.approx(180 * US)
    # nothing but their parent overlaps the two all-reduces: all exposed;
    # %fusion.1 has an all-reduce among its operands and is no collective
    assert d0["collective_exposed_s"] == pytest.approx(180 * US)
    assert d0["mosaic_s"] == pytest.approx(80 * US)
    assert d0["program_runs"] == 2
    # device 1: an asynchronous all-reduce [300,500) beside the operations;
    # %fusion.1 hides it until 400, the X64Combine custom call after that
    # (a custom call, but no Mosaic kernel)
    assert d1["collective_s"] == pytest.approx(200 * US)
    assert d1["collective_exposed_s"] == pytest.approx(0.0)
    assert d1["mosaic_s"] == 0.0
    assert reduced["collective_exposed_s"] == pytest.approx(90 * US)


def test_gaps_are_charged_to_what_the_host_was_doing(reduced):
    gaps = {(round(s / US), round(e / US)): label
            for s, e, label in reduced["devices"]["/device:TPU:0"]["gaps"]}
    assert gaps == {
        (50, 100): tr.NO_SPAN,                  # before the first program
        (400, 420): tr.IN_PROGRAM,              # inside a running program
        (500, 560): "loader",
        (560, 690): "executor.submit",
        (690, 700): tr.NO_SPAN,
        (1000, 1100): tr.IN_PROGRAM,
        (1100, 1150): "fetch_wait",
    }


def test_breakdown_ranks_ops_and_gaps(reduced):
    b = tr.breakdown(reduced)
    assert b["device_ops"][0][0] == "fusion(kOutput)"
    assert b["device_ops"][0][1] == pytest.approx(250 * US)  # (200+300)/2
    # device 1 idles most: [50,100) and [600,1150), by what the host did
    assert dict(b["idle_gaps"]) == pytest.approx({
        tr.NO_SPAN: (50 + 410) * US, "executor.submit": 90 * US,
        "fetch_wait": 50 * US})
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_interval_arithmetic():
    assert tr.union([(3, 5), (1, 2), (2, 4)]) == [(1, 5)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    # a collective half hidden behind compute is half exposed
    assert tr.length(tr.subtract([(0, 10)], [(5, 20)])) == 5


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce({"devices": {}})
    with pytest.raises(ValueError, match="no operation ran"):
        tr.reduce({"devices": {"/device:TPU:0": {"ops": [], "async": [],
                                                 "modules": []}}},
                  [("window", 0.0, 1.0)])
