"""The eight per-layer metrics of the executor's own record
(``harness/step_records.py`` and their reader files) on a hand-made ring and
hand-made idle pieces, and on one tiny cell run on the CPU.  Microseconds
below; every number can be computed by hand."""
import os
import time

import pytest

from benchmark.harness import step_records
from benchmark.harness.registry import Registry
from benchmark.harness.spans import Spans
from benchmark.tests.conftest import TINY

US = 1e-6
EPOCH_NS = 1_790_000_000_000_000_000       # the records' zero on the wall
PHASE_READERS = ["executor.run_ms_p50", "executor.state_ms_p50",
                 "executor.stage_ms_p50", "executor.call_ms_p50"]
SETUP_READERS = ["executor.setup_trace_s", "executor.setup_compile_s",
                 "executor.setup_cache_misses"]
NEW = PHASE_READERS + ["executor.idle_named_share"] + SETUP_READERS


def _reader(name):
    return Registry().module("layer_metrics", name + ".py").read


def _step(step, t0_us, call_us, miss=False):
    """A step record whose phases are 10 / 100 / 30 / 0 / 40 / call / 60 /
    20 us, in the program's order."""
    phases = {"resolve": 10.0, "gather": 100.0, "stage": 30.0,
              "persist": 0.0, "place": 40.0, "call": float(call_us),
              "scatter": 60.0, "fetch": 20.0}
    run_us = sum(phases.values())
    return {"kind": "step", "step": step, "t0_us": float(t0_us),
            "run_us": run_us, "phases_us": phases, "compile_miss": miss,
            "dur_us": call_us + 40.0, "ts_us": t0_us + run_us + 1.0}


# set-up: two Executor misses (one loaded from the cache, one compiled),
# the reference's executable outside any miss, two small eager programs;
# the window's three steps at 1.0, 1.002 and 1.004 s with calls of 500,
# 700 and 900 us; one warm-up step before it, one step after it, and the
# op-map compile that a traced run asks for after the window
RING = [
    {"kind": "xla_compile", "t0_us": 100_000.0, "backend_us": 3e6,
     "cache_hit": True, "retrieval_us": 2.9e6},
    {"kind": "compile", "fp": "aaa", "n_ops": 7, "t0_us": 90_000.0,
     "total_us": 20e6, "prepare_us": 1e6, "backend_us": 3e6,
     "cache_hit": True, "xla_compiles": 1},
    {"kind": "xla_compile", "t0_us": 30e6, "backend_us": 54e6,
     "cache_hit": False},
    {"kind": "xla_compile", "t0_us": 85e6, "backend_us": 4_000.0,
     "cache_hit": False},
    {"kind": "compile", "fp": "bbb", "n_ops": 9, "t0_us": 25e6,
     "total_us": 71e6, "prepare_us": 2e6, "backend_us": 54e6 + 4_000.0,
     "cache_hit": False, "xla_compiles": 2},
    {"kind": "xla_compile", "t0_us": 97e6, "backend_us": 2e6,
     "cache_hit": False},
    {"kind": "xla_compile", "t0_us": 99.5e6, "backend_us": 8_000.0,
     "cache_hit": False},
    _step(0, 99.9e6, 5_000, miss=False),
    _step(1, 100.000e6, 500),
    _step(2, 100.002e6, 700),
    _step(3, 100.004e6, 900),
    _step(4, 100.1e6, 10_000),
    {"kind": "xla_compile", "t0_us": 101e6, "backend_us": 30e6,
     "cache_hit": False},
]


def _spans(extra=()):
    spans = Spans()
    # the window's submit spans hold the records' starts: each began 50 us
    # before its run did and lasted 2 ms
    spans.records = [("loader", EPOCH_NS + int(99.9995e9), 100 * US)] + [
        ("executor.submit", EPOCH_NS + int((100.0e6 + k * 2_000 - 50) * 1e3),
         2_000 * US) for k in range(3)] + list(extra)
    return spans


@pytest.fixture
def ctx(monkeypatch):
    from paddle_tpu.fluid import flight_recorder, trace
    rec = flight_recorder.FlightRecorder(capacity=64)
    monkeypatch.setattr(flight_recorder, "_recorder", rec)
    monkeypatch.setattr(trace._state, "epoch_wall_ns", EPOCH_NS)
    monkeypatch.setattr(rec, "snapshot", lambda last=None: [
        dict(r) for r in RING])
    return {"cell": {"name": "bert_base_seq128"}, "spans": _spans(),
            "trace": None, "traced_steps": 0}


@pytest.mark.parametrize("name, want", [
    # run_us of the window's steps: 260 + call = 760, 960, 1160 us
    ("executor.run_ms_p50", 0.960),
    ("executor.state_ms_p50", 0.160),          # gather 100 + scatter 60
    ("executor.stage_ms_p50", 0.070),          # stage 30 + place 40
    ("executor.call_ms_p50", 0.700),
    # the two misses' total less backend: 17 + (71 - 54.004) s
    ("executor.setup_trace_s", 17.0 + 16.996),
    # every executable before the window: 3 + 54 + 0.004 + 2 + 0.008 s
    ("executor.setup_compile_s", 59.012),
    # 54 s and 2 s were compiled; the 3 s one was loaded, two are small
    ("executor.setup_cache_misses", 2),
])
def test_reader_on_the_hand_made_ring(ctx, name, want):
    assert _reader(name)(ctx) == pytest.approx(want, rel=1e-9)


def test_the_window_is_the_submit_spans(ctx):
    v = step_records.view(ctx)
    assert [r["step"] for r in v["steps"]] == [1, 2, 3]
    # the op-map compile after the window belongs to no set-up sum
    assert all(r["t0_us"] < 100e6 for r in v["xla"] + v["compiles"])
    assert len(v["xla"]) == 5 and len(v["compiles"]) == 2


def test_idle_inside_a_phase(ctx, monkeypatch):
    """Three idle pieces charged to ``executor.submit``: 100 us that are
    step 1's ``gather`` to the microsecond, 60 us half inside the end of
    step 2's ``run`` (10 of ``scatter``, 20 of ``fetch``) and half after
    it, 40 us before step 3's ``run`` began; one piece under another label,
    which is not counted."""
    session = EPOCH_NS + 99_000_000_000          # the session began at 99 s

    def at(us):                  # us after step 1's t0 -> timeline seconds
        return (100.0e6 + us - 99e6) * US

    end2 = 2_000 + 960                           # step 2's run ends here
    gaps = [(at(10), at(110), "executor.submit"),
            (at(end2 - 30), at(end2 + 30), "executor.submit"),
            (at(3_950), at(3_990), "executor.submit"),
            (at(500), at(700), "in_program")]
    idle = step_records.idle_by_phase(
        gaps, step_records.phase_intervals(
            step_records.view(ctx)["steps"], EPOCH_NS, session))
    assert idle["submit_s"] == pytest.approx(200 * US)
    assert idle["by_phase"] == {"gather": pytest.approx(100 * US),
                                "scatter": pytest.approx(10 * US),
                                "fetch": pytest.approx(20 * US)}

    monkeypatch.setattr(step_records, "_load_idle", lambda c: idle)
    ctx["trace"] = {"devices": {}}
    assert _reader("executor.idle_named_share")(ctx) == pytest.approx(65.0)


def test_idle_outside_every_phase_is_before_or_after_run(ctx):
    """The loop's span began 50 us before each ``run`` and outlasted it."""
    session = EPOCH_NS + 99_000_000_000
    steps = step_records.view(ctx)["steps"]
    submits = [(s, e) for n, s, e in ctx["spans"].on_timeline(session)
               if n == "executor.submit"]
    iv = step_records.around_run(steps, submits, EPOCH_NS, session)
    assert [n for n, _, _ in iv] == [step_records.BEFORE_RUN,
                                     step_records.AFTER_RUN] * 3
    assert iv[0][2] - iv[0][1] == pytest.approx(50 * US)
    # step 1's run is 760 us of a span of 2000 that began 50 us early
    assert iv[1][2] - iv[1][1] == pytest.approx((2000 - 50 - 760) * US)
    gaps = [(1.0 - 40 * US, 1.0 + 5 * US, "executor.submit"),
            (1.0 + 1000 * US, 1.0 + 1100 * US, "executor.submit")]
    idle = step_records.idle_by_phase(gaps, iv)["by_phase"]
    assert idle == {step_records.BEFORE_RUN: pytest.approx(40 * US),
                    step_records.AFTER_RUN: pytest.approx(100 * US)}


def test_the_session_start_comes_from_the_slices_trace(ctx, tmp_path,
                                                        monkeypatch):
    """End to end on the fixture trace the program's own tests use (its
    session began at the records' zero): the idlest device's pieces, the
    records and the loop's spans on one timeline."""
    from jax.profiler import ProfileData
    from benchmark.harness import program_ops
    from benchmark.harness.registry import ROOT
    d = tmp_path / "plugins" / "profile" / "2026_10_04"
    d.mkdir(parents=True)
    with open(os.path.join(ROOT, "tests", "data",
                           "op_map_trace.textproto")) as f:
        (d / "host.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    monkeypatch.setattr(program_ops, "trace_dir", lambda c: str(tmp_path))
    t0 = 100.0                       # step 1's run began 100 s after zero
    ctx["trace"] = {"devices": {
        "/device:TPU:0": {"gaps": [(t0 + 10 * US, t0 + 110 * US,
                                    "executor.submit"),
                                   (t0 - 30 * US, t0 - 10 * US,
                                    "executor.submit")]},
        "/device:TPU:1": {"gaps": [(t0, t0 + 5 * US, "executor.submit")]}}}
    assert _reader("executor.idle_named_share")(ctx) == pytest.approx(
        100.0 * 100 / 120)
    idle = ctx["step_records.idle"]
    assert idle["by_phase"] == {"gather": pytest.approx(100 * US)}
    assert idle["around"] == {
        step_records.BEFORE_RUN: pytest.approx(20 * US)}


def test_a_compile_miss_has_no_order_of_phases():
    miss = _step(0, 0.0, 100, miss=True)
    assert step_records.phase_intervals([miss], EPOCH_NS, EPOCH_NS) == []
    iv = step_records.phase_intervals([_step(1, 1e6, 100)], EPOCH_NS,
                                      EPOCH_NS)
    assert [n for n, _, _ in iv] == list(_step(1, 0, 0)["phases_us"])
    assert iv[0][1] == pytest.approx(1.0)
    assert iv[-1][2] == pytest.approx(1.0 + 360 * US)


def test_the_table_is_printed_once(ctx, monkeypatch, capsys):
    monkeypatch.setattr(step_records, "_load_idle", lambda c: {
        "submit_s": 0.002, "by_phase": {"call": 0.0015}})
    ctx["trace"] = {"devices": {}}
    for name in NEW:
        _reader(name)(ctx)
    err = capsys.readouterr().err
    assert err.count("[executor_phases] 3 step records in the window") == 1
    assert "run p50 0.960 ms" in err and "0 records whose phases" in err
    row = next(l for l in err.splitlines() if " call " in l)
    assert row.split()[1:] == ["call", "0.700", "0.700", "72.92", "0.0015"]
    assert "bbb (9 ops) python 17.00 s, xla 54.00 s compiled" in err
    assert "54.0 s miss" in err and "3.0 s hit" in err


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_on_a_ring_without_phases(ctx, monkeypatch,
                                                      name):
    """The parent's records: steps with ``dur_us`` alone, no compile
    records."""
    from paddle_tpu.fluid import flight_recorder
    old = [{"kind": "step", "step": r["step"], "dur_us": r["dur_us"],
            "ts_us": r["ts_us"]} for r in RING if r["kind"] == "step"]
    monkeypatch.setattr(flight_recorder.recorder(), "snapshot",
                        lambda last=None: old)
    assert _reader(name)(ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_on_a_program_without_the_epoch(ctx, monkeypatch,
                                                            name):
    from paddle_tpu.fluid import trace
    monkeypatch.delattr(trace, "epoch_unix_ns")
    assert _reader(name)(ctx) is None


def test_a_ring_that_lost_records_gives_no_partial_sum(ctx, monkeypatch,
                                                       capsys):
    from paddle_tpu.fluid import flight_recorder
    rec = flight_recorder.recorder()
    monkeypatch.setattr(type(rec), "total", property(lambda self: 70))
    for name in SETUP_READERS:
        assert _reader(name)(ctx) is None
    assert _reader("executor.run_ms_p50")(ctx) == pytest.approx(0.960)
    assert "lost its oldest 6 records" in capsys.readouterr().err
    # and where the ring no longer reaches back to the window's start
    monkeypatch.setattr(rec, "snapshot", lambda last=None: [
        dict(r) for r in RING[9:]])
    ctx.pop("step_records")
    assert _reader("executor.run_ms_p50")(ctx) is None


def test_benchmark_json_lists_the_readers_for_every_cell():
    reg = Registry()
    entries = {m["name"]: m for m in reg.spec["per_layer"]}
    for name in NEW:
        m = entries[name]
        assert m["layer"] == "executor" and "workloads" not in m
        assert m["moves"] == ("setup_s" if name in SETUP_READERS
                              else "samples_per_s_per_chip")
        assert os.path.exists(os.path.join(
            reg.bench_dir, "layer_metrics", name + ".py"))


def test_on_a_tiny_cell_run_untraced(tmp_path):
    """One real run on the CPU: the program's records, the loop's spans."""
    reg = Registry()
    cell = reg.cell("bert_base_seq128")
    cfg, cfg_dir = reg.config(cell["config"])
    mix = reg.mix(cell["traffic"])
    from paddle_tpu.fluid import flight_recorder
    flight_recorder.reset()              # other tests' records fill the ring
    cfg.update(TINY[cell["config"]]["config"])
    mix.update(TINY[cell["config"]]["mix"])
    loop = reg.module("loops", cfg["loop"] + ".py")
    res = loop.run(cell, cfg, cfg_dir, mix, reg, 13, 1.0, 0,
                   time.perf_counter(), allow_cpu=True,
                   out_dir=str(tmp_path))
    ctx = res["layer_ctx"]
    values = {name: _reader(name)(ctx) for name in NEW}
    assert values["executor.idle_named_share"] is None      # no trace
    for name in PHASE_READERS + SETUP_READERS:
        assert values[name] is not None and values[name] >= 0, name
    v = step_records.view(ctx)
    # one record per step of the window, each inside a submit span
    assert len(v["steps"]) == res["attempted"] == len(ctx["steps"])
    for r in v["steps"]:
        assert sum(r["phases_us"].values()) == pytest.approx(r["run_us"],
                                                             rel=0.02)
    # the training step, the check's program and the startup program each
    # missed once, all before the window
    assert len(v["compiles"]) >= 3
    assert values["executor.run_ms_p50"] >= values["executor.call_ms_p50"]
    outside = _reader("executor.dispatch_ms_p50")(ctx)
    assert values["executor.run_ms_p50"] <= outside * 1.05 + 0.1
