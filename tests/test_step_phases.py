"""Where the host's time of an ``Executor.run`` went: the step clock's
phases in the flight recorder's step record and as spans, and the compile
record that splits ``executor.compile_seconds`` into XLA's share and the
Python side (fluid/executor.py ``_StepClock``, fluid/flight_recorder.py,
docs/observability.md "Where a step's host time goes")."""
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import executor as executor_mod
from paddle_tpu.fluid import flight_recorder, trace
from paddle_tpu.fluid.async_pipeline import AsyncStepRunner
from paddle_tpu.fluid.core import Scope, scope_guard
from paddle_tpu.fluid.executor import PHASES
from paddle_tpu.fluid.framework import reset_unique_name


@pytest.fixture(autouse=True)
def clean_plane():
    reset_unique_name()
    trace.reset_all()
    flight_recorder.reset()
    flight_recorder.configure(enabled=True)
    yield
    trace.disable()
    trace.reset_all()
    flight_recorder.reset()
    flight_recorder.configure(enabled=True)


def _mlp(width=16):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, width])
        h = fluid.layers.fc(x, 32, act="relu")
        loss = fluid.layers.mean(fluid.layers.fc(h, 4))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    return main, startup, loss


def _feed(n=8, width=16):
    return {"x": np.ones((n, width), "float32")}


def _records(kind):
    return [r for r in flight_recorder.recorder().snapshot()
            if r.get("kind") == kind]


def _drive(how, exe, main, loss, steps=3):
    """``steps`` steps through one of the Executor's three doors."""
    if how == "run":
        for _ in range(steps):
            exe.run(main, feed=_feed(), fetch_list=[loss])
    elif how == "run_scan":
        for _ in range(steps):
            exe.run_scan(main, [_feed(), _feed()], fetch_list=[loss])
    else:
        runner = AsyncStepRunner(exe, main, [loss])
        for _ in range(steps):
            runner.submit(_feed())
        runner.drain()


# ---------------------------------------------------------------------------
# the step record
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["run", "run_scan", "async"])
def test_step_record_holds_the_eight_phases(how):
    main, startup, loss = _mlp()
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        flight_recorder.reset()
        _drive(how, exe, main, loss)
    steps = _records("step")
    assert len(steps) == 3
    assert [r["compile_miss"] for r in steps] == [True, False, False]
    for r in steps:
        ph = r["phases_us"]
        assert set(PHASES) <= set(ph)
        assert all(v >= 0 for v in ph.values())
        assert sum(ph.values()) == pytest.approx(r["run_us"], rel=0.02)
        assert r["t0_us"] + r["run_us"] <= r["ts_us"]
        # dur_us keeps its meaning: the call (with a plan's placing)
        assert r["dur_us"] == pytest.approx(ph["place"] + ph["call"],
                                            abs=0.1)
        # nothing donates on the CPU and no plan places anything
        assert ph["persist"] == 0 and ph["place"] == 0
        assert ph["call"] > 0 and ph["resolve"] > 0 and ph["stage"] > 0
        assert (r.get("scan") == 2) == (how == "run_scan")
    # a miss counts _prepare apart from the eight; a hit has just the eight
    assert steps[0]["phases_us"]["prepare"] > 0
    assert list(steps[1]["phases_us"]) == list(PHASES)
    # steps follow each other on the record's clock
    assert steps[0]["t0_us"] + steps[0]["run_us"] <= steps[1]["t0_us"]


def test_t0_is_on_the_wall_clock():
    main, startup, loss = _mlp()
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])
        before = time.time_ns()
        exe.run(main, feed=_feed(), fetch_list=[loss])
        after = time.time_ns()
    r = _records("step")[-1]
    wall = trace.epoch_unix_ns() + r["t0_us"] * 1e3
    assert before - 5e6 <= wall <= after + 5e6
    assert trace.epoch_unix_ns() == trace._state.epoch_wall_ns


@pytest.mark.parametrize("n_dev, placed", [(None, False), (1, True),
                                           (4, True)])
def test_place_is_a_plans_and_only_a_plans(n_dev, placed):
    main, startup, loss = _mlp()
    program = main
    if n_dev is not None:
        bs = fluid.BuildStrategy()
        bs.sharding = "dp"
        bs.sharding_mesh = {"dp": n_dev}
        program = fluid.CompiledProgram(main, build_strategy=bs)
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        flight_recorder.reset()
        for _ in range(3):
            exe.run(program, feed=_feed(), fetch_list=[loss])
    for r in _records("step"):
        assert (r["phases_us"]["place"] > 0) == placed
        assert sum(r["phases_us"].values()) == pytest.approx(r["run_us"],
                                                             rel=0.02)


def test_wrapped_place_is_the_seam():
    """``jitted(*wrapped.place(...))`` is ``wrapped(...)``: the Executor
    stamps between the two."""
    import jax
    from paddle_tpu.parallel import mesh as mesh_registry, sharding as shd
    mesh = mesh_registry.build_mesh({"dp": 2}, devices=jax.devices()[:2])
    plan = shd.ShardingPlan(mesh, [(r".*", None)], mode="dp")

    def fn(mut, ro, feeds, key):
        return [feeds["x"].sum() + mut["w"].sum()], {"w": mut["w"] + 1}

    w = np.ones((4, 4), "float32")
    wrapped, jitted = shd.wrap_with_plan(
        fn, plan, {"w": w}, ["w"], [], {"x": np.ones((8, 4), "float32")})
    args = ({"w": w}, {}, {"x": np.ones((8, 4), "float32")},
            jax.random.PRNGKey(0))
    placed = wrapped.place(*args)
    assert placed[2]["x"].sharding.is_equivalent_to(
        wrapped.in_shardings[2]["x"], 2)
    a, b = jitted(*placed), wrapped(*args)
    assert float(a[0][0]) == float(b[0][0]) == 48.0


@pytest.mark.parametrize("recorder_on, tracing_on, made", [
    (False, False, 0), (True, False, 2), (False, True, 2), (True, True, 2)])
def test_no_clock_is_made_with_both_off(monkeypatch, recorder_on,
                                        tracing_on, made):
    main, startup, loss = _mlp()
    exe = fluid.Executor()
    built = []

    class Counting(executor_mod._StepClock):
        def __init__(self):
            built.append(1)
            super().__init__()

    with scope_guard(Scope()):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])
        monkeypatch.setattr(executor_mod, "_StepClock", Counting)
        flight_recorder.reset()
        flight_recorder.configure(enabled=recorder_on)
        if tracing_on:
            trace.enable()
        exe.run(main, feed=_feed(), fetch_list=[loss])
        exe.run_scan(main, [_feed(), _feed()], fetch_list=[loss])
    assert len(built) == made
    assert len(_records("step")) == (2 if recorder_on else 0)
    runs = [e for e in trace.get_events() if e["name"] == "executor::run"]
    assert len(runs) == (2 if tracing_on else 0)


def test_a_failing_fetch_still_leaves_the_steps_record():
    """The record is forensic: a NaN found while fetching must not take the
    step that produced it out of the ring."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        y = fluid.layers.log(x)
    exe = fluid.Executor()
    fluid.core.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with scope_guard(Scope()):
            with pytest.raises(Exception):
                exe.run(main, feed={"x": -np.ones((2, 4), "float32")},
                        fetch_list=[y])
    finally:
        fluid.core.set_flags({"FLAGS_check_nan_inf": False})
    steps = _records("step")
    assert len(steps) == 1 and steps[0]["phases_us"]["call"] > 0
    assert sum(steps[0]["phases_us"].values()) == pytest.approx(
        steps[0]["run_us"], rel=0.02)


# ---------------------------------------------------------------------------
# the spans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["run", "run_scan"])
def test_run_is_the_parent_of_its_phases(how):
    main, startup, loss = _mlp()
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        trace.enable()
        flight_recorder.reset()
        _drive(how, exe, main, loss)
    evs = [e for e in trace.get_events() if e.get("ph") == "X"]
    runs = [e for e in evs if e["name"] == "executor::run"]
    steps = [e for e in evs if e["name"] == "executor::step"]
    kids = [e for e in evs if e["name"].startswith("executor::run/")]
    assert len(runs) == 3 and len(steps) == 3      # not doubled
    assert [e["args"]["step"] for e in runs] \
        == [e["args"]["step"] for e in steps] \
        == [r["step"] for r in _records("step")]
    eps = 1e-3                                     # us: float rounding
    for run, step, rec in zip(runs, steps, _records("step")):
        mine = [k for k in kids if run["ts"] - eps <= k["ts"]
                and k["ts"] + k["dur"] <= run["ts"] + run["dur"] + eps]
        names = [k["name"].split("/", 1)[1] for k in mine]
        # the phases whose work happened, in order, tiling the parent
        assert [n for n in PHASES if n in names] \
            == [n for n in names if n in PHASES]
        assert {"resolve", "gather", "stage", "call", "scatter",
                "fetch"} <= set(names)
        assert sum(k["dur"] for k in mine) == pytest.approx(run["dur"],
                                                            rel=1e-6)
        assert all(k["tid"] == run["tid"] for k in mine)
        # the same stamps in the span and in the record
        assert run["ts"] == pytest.approx(rec["t0_us"], abs=eps)
        assert run["dur"] == pytest.approx(rec["run_us"], abs=eps)
        call = next(k for k in mine if k["name"] == "executor::run/call")
        assert step["ts"] == pytest.approx(call["ts"], abs=eps)
        assert step["dur"] == pytest.approx(rec["dur_us"], abs=0.1)
    assert len(kids) == sum(len([k for k in kids
                                 if r["ts"] - eps <= k["ts"]
                                 <= r["ts"] + r["dur"] + eps])
                            for r in runs)         # no orphan


# ---------------------------------------------------------------------------
# the compile record
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["run", "run_scan"])
def test_a_miss_writes_one_compile_record_and_a_hit_none(how):
    main, startup, loss = _mlp(width=24)     # a shape no other test compiled
    exe = fluid.Executor()
    h = trace.metrics().histogram("executor.compile_seconds")
    xla_h = trace.metrics().histogram("xla.backend_compile_seconds")
    with scope_guard(Scope()):
        exe.run(startup)
        flight_recorder.reset()
        trace.enable()
        seen, seen_s, xla_seen = h.count, h.total, xla_h.count
        _drive_w(how, exe, main, loss, 24, steps=1)
        compiles, xla = _records("compile"), _records("xla_compile")
        assert len(compiles) == 1 and h.count == seen + 1
        c = compiles[0]
        assert c["fp"] == _records("step")[0]["fp"] and c["n_ops"] > 0
        assert 0 < c["prepare_us"] <= c["total_us"]
        assert 0 < c["backend_us"] <= c["total_us"]
        # one reading: what executor.compile_seconds observed
        assert c["total_us"] == pytest.approx((h.total - seen_s) * 1e6,
                                              abs=0.1)
        assert (c.get("scan") == 2) == (how == "run_scan")
        inside = [x for x in xla if c["t0_us"] <= x["t0_us"]
                  and x["t0_us"] + x["backend_us"]
                  <= c["t0_us"] + c["total_us"]]
        assert len(inside) >= 1 and len(inside) == c["xla_compiles"]
        assert sum(x["backend_us"] for x in inside) == pytest.approx(
            c["backend_us"], abs=1.0)
        assert xla_h.count == xla_seen + len(xla)
        # neither is progress: the watchdog's count is the steps'
        assert flight_recorder.recorder().completions \
            == len(_records("step"))
        # the span carries the record's fields
        span = [e for e in trace.get_events()
                if e["name"] == "executor::compile"]
        assert len(span) == 1
        for k in ("total_us", "prepare_us", "backend_us", "cache_hit",
                  "xla_compiles", "n_ops"):
            assert span[0]["args"][k] == c[k]
        assert span[0]["args"]["fingerprint"] == c["fp"]
        assert span[0]["dur"] == pytest.approx(c["total_us"], abs=0.1)
        # an Executor cache hit writes neither
        _drive_w(how, exe, main, loss, 24, steps=2)
        assert len(_records("compile")) == 1
        assert len(_records("xla_compile")) == len(xla)
        assert h.count == seen + 1


def _drive_w(how, exe, main, loss, width, steps):
    for _ in range(steps):
        if how == "run":
            exe.run(main, feed=_feed(width=width), fetch_list=[loss])
        else:
            exe.run_scan(main, [_feed(width=width)] * 2, fetch_list=[loss])


def test_the_listeners_are_registered_once():
    from jax._src import monitoring
    for _ in range(3):
        fluid.Executor()
        flight_recorder.watch_xla_compiles()
    durations = [f for f in monitoring.get_event_duration_listeners()
                 if f is flight_recorder._on_duration]
    events = [f for f in monitoring.get_event_listeners()
              if f is flight_recorder._on_event]
    assert len(durations) == 1 and len(events) == 1


@pytest.mark.parametrize("hit, retrieval", [(False, None), (True, 0.25)])
def test_an_xla_compile_record_says_compiled_or_loaded(hit, retrieval):
    """jax reports a cache hit and its retrieval time from inside the block
    it times as the backend compile (``compiler.compile_or_get_cached``):
    the events are replayed here in jax's order."""
    hits = trace.metrics().counter("xla.persistent_cache_hits")
    n0 = hits.value
    t_before = trace.elapsed_us()
    if hit:
        flight_recorder._on_event("/jax/compilation_cache/cache_hits")
        flight_recorder._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", retrieval)
    flight_recorder._on_duration(
        "/jax/core/compile/jaxpr_trace_duration", 9.0)      # not summed
    flight_recorder._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.5)
    flight_recorder._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.002)
    first, second = _records("xla_compile")
    assert first["backend_us"] == 5e5 and first["cache_hit"] is hit
    assert first["t0_us"] == pytest.approx(t_before - 5e5, abs=5e4)
    assert first.get("retrieval_us") == (2.5e5 if hit else None)
    # the hit belonged to the first executable alone
    assert second["cache_hit"] is False and "retrieval_us" not in second
    assert hits.value == n0 + (1 if hit else 0)
    assert flight_recorder.recorder().completions == 0


def test_a_compile_record_takes_the_largest_executables_word():
    """Inside one miss: the step's executable came from the cache, a small
    eager program beside it was compiled (those are never cached)."""
    t0 = trace.now()
    flight_recorder._on_event("/jax/compilation_cache/cache_hits")
    flight_recorder._on_duration(
        "/jax/core/compile/backend_compile_duration", 1e-6)
    flight_recorder._on_duration(
        "/jax/core/compile/backend_compile_duration", 1e-7)
    fields = flight_recorder.record_compile("abc", 5, t0, 1e4, 1e3)
    assert fields["cache_hit"] is True and fields["xla_compiles"] == 2
    assert fields["backend_us"] == pytest.approx(1.1)
    rec = _records("compile")[0]
    assert rec["fp"] == "abc" and rec["t0_us"] == trace._ts_us(t0)
    # what began before the miss is not the miss's
    later = flight_recorder.record_compile("abc", 5, trace.now(), 1.0, 1.0)
    assert later["xla_compiles"] == 0 and later["cache_hit"] is False


# ---------------------------------------------------------------------------
# an operator reads it
# ---------------------------------------------------------------------------

def test_diagnose_prints_the_run_and_the_compiles():
    import importlib.util
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "diagnose", os.path.join(root, "tools", "diagnose.py"))
    diag = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(diag)
    main, startup, loss = _mlp()
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        flight_recorder.reset()
        _drive("run", exe, main, loss)
    wide = flight_recorder.recorder().snapshot()
    lines = diag._wide_event_section({"wide_events": wide})
    last = _records("step")[-1]
    top = max(last["phases_us"], key=last["phases_us"].get)
    run_line = [l for l in lines if "its run" in l]
    assert len(run_line) == 1 and f"'{top}'" in run_line[0]
    assert f"{last['run_us'] / 1e3:.1f}ms" in run_line[0]
    compile_lines = [l for l in lines if l.lstrip().startswith("compile")]
    assert len(compile_lines) == 1
    assert _records("compile")[0]["fp"] in compile_lines[0]
    assert "python" in compile_lines[0] and "xla" in compile_lines[0]
    assert "persistent cache" in compile_lines[0]
    # a bundle written before these fields renders as it always did
    old = [{k: v for k, v in r.items()
            if k not in ("phases_us", "run_us", "t0_us")}
           for r in wide if r["kind"] == "step"]
    assert not any("its run" in l or "compile  :" in l
                   for l in diag._wide_event_section({"wide_events": old}))
