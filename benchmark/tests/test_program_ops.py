"""The eight per-layer metrics by Program op (``harness/program_ops.py`` and
their reader files) on the pair of fixtures the program's own tests use:
``tests/data/op_map_fixture.hlo.txt`` (an executable's text) and
``tests/data/op_map_trace.textproto`` (a trace with its instruction names).
Microseconds below; every number can be computed by hand from the trace."""
import os

import pytest

from benchmark.harness import program_ops
from benchmark.harness.registry import ROOT, Registry
from benchmark.harness.spans import Spans

US = 1e-6
DATA = os.path.join(ROOT, "tests", "data")
SESSION_START_NS = 1_790_000_000_000_000_000
NEW = ["kernel.attributed_share", "kernel.mxu_op_share",
       "device.forward_ms_per_step", "device.backward_ms_per_step",
       "device.optimizer_ms_per_step", "kernel.dropout_ms_per_step",
       "kernel.softmax_ms_per_step", "kernel.norm_ms_per_step"]


def _reader(name):
    return Registry().module("layer_metrics", name + ".py").read


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    """What the loop leaves a reader, with the fixture trace where the loop
    would have put the slice's and the fixture's op map as the program's."""
    from jax.profiler import ProfileData
    from paddle_tpu.fluid import device_stats
    d = tmp_path / "plugins" / "profile" / "2026_09_28"
    d.mkdir(parents=True)
    with open(os.path.join(DATA, "op_map_trace.textproto")) as f:
        (d / "host.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    with open(os.path.join(DATA, "op_map_fixture.hlo.txt")) as f:
        op_map = device_stats.hlo_op_map(f.read())
    monkeypatch.setattr(program_ops, "trace_dir", lambda ctx: str(tmp_path))
    monkeypatch.setattr(device_stats, "op_maps", lambda: [
        {"label": "step", "module": "jit_fn", "map": op_map}])
    spans = Spans()
    # the window span around the traced slice: [40, 1200) us of the session
    spans.records = [("window", SESSION_START_NS + 40_000, 1160 * US)]
    return {"cell": {"name": "bert_base_seq128"}, "spans": spans,
            "trace": {"busy_s": 671 * US}, "traced_steps": 2}


@pytest.mark.parametrize("name, want", [
    # device 0: 842 us busy, 780 charged to an op; device 1: 500 of 500
    ("kernel.attributed_share", 100.0 * 640 / 671),
    ("kernel.mxu_op_share", 100.0 * 225 / 671),
    # per step: the means over the two devices, over the slice's 2 steps
    ("device.forward_ms_per_step", 1e3 * 375 * US / 2),
    ("device.backward_ms_per_step", 1e3 * 240 * US / 2),
    ("device.optimizer_ms_per_step", 1e3 * 25 * US / 2),
    # the Mosaic kernel, twice on device 0 (80 + 100 us)
    ("kernel.dropout_ms_per_step", 1e3 * 90 * US / 2),
    ("kernel.softmax_ms_per_step", 0.0),
    # %fusion.1 on both devices (100 + 300 us)
    ("kernel.norm_ms_per_step", 1e3 * 200 * US / 2),
])
def test_reader_on_the_fixtures(ctx, name, want):
    assert _reader(name)(ctx) == pytest.approx(want)


def test_roles_add_up_to_the_attributed_busy_time(ctx):
    roles = sum(_reader(f"device.{r}_ms_per_step")(ctx)
                for r in ("forward", "backward", "optimizer"))
    busy_ms = 1e3 * ctx["trace"]["busy_s"] / ctx["traced_steps"]
    assert roles == pytest.approx(
        _reader("kernel.attributed_share")(ctx) / 100.0 * busy_ms)


def test_the_trace_is_loaded_once_and_the_top_is_printed(ctx, monkeypatch,
                                                         capsys):
    from paddle_tpu.fluid import device_stats
    calls = []
    real = device_stats.device_time_by_op
    monkeypatch.setattr(device_stats, "device_time_by_op",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    for name in NEW:
        _reader(name)(ctx)
    assert len(calls) == 1
    err = capsys.readouterr().err
    assert "mul_grad" in err and "also adam" in err
    assert "layer_norm_0.tmp_2" in err            # an instance
    assert "jit__threefry_fold_in/fusion " in err  # without a Program op


def test_stale_names_are_called_out(ctx, monkeypatch, capsys):
    """An executable out of a compile cache that another tree warmed has the
    instruction names but none of the scopes."""
    from paddle_tpu.fluid import device_stats
    stale = {k: None for k in device_stats.op_maps()[0]["map"]}
    monkeypatch.setattr(device_stats, "op_maps", lambda: [
        {"label": "step", "module": "jit_fn", "map": stale}])
    assert _reader("kernel.attributed_share")(ctx) == 0.0
    assert "Clear the compile cache" in capsys.readouterr().err


def test_the_window_span_cuts_the_slice(ctx):
    # the first run of the step alone
    ctx["spans"].records = [("window", SESSION_START_NS + 90_000, 370 * US)]
    ctx["traced_steps"] = 1
    assert _reader("kernel.attributed_share")(ctx) == pytest.approx(100.0)
    assert _reader("kernel.dropout_ms_per_step")(ctx) \
        == pytest.approx(1e3 * 20 * US)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("why", ["no trace", "no steps", "no file",
                                 "no device plane", "no instrument"])
def test_reader_returns_none(ctx, monkeypatch, tmp_path, name, why):
    from paddle_tpu.fluid import device_stats
    if why == "no trace":
        ctx["trace"] = None
    elif why == "no steps":
        ctx["traced_steps"] = 0
    elif why == "no file":
        monkeypatch.setattr(program_ops, "trace_dir",
                            lambda ctx: str(tmp_path / "empty"))
    elif why == "no device plane":
        from jax.profiler import ProfileData
        path = os.path.join(program_ops.trace_dir(ctx), "plugins", "profile",
                            "2026_09_28", "host.xplane.pb")
        with open(path, "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(
                'planes { id: 1 name: "/host:CPU" }'))
    else:                            # the parent commit's program
        monkeypatch.delattr(device_stats, "device_time_by_op")
    assert _reader(name)(ctx) is None


def test_benchmark_json_lists_the_readers_and_their_cells():
    reg = Registry()
    by_name = {m["name"]: m for m in reg.spec["per_layer"]}
    assert [m["name"] for m in reg.spec["per_layer"]][-8:] == NEW
    bert = ["bert_base_seq128", "bert_base_seq512", "bert_base_seq128_dp4"]
    for name in NEW:
        m = by_name[name]
        assert m["source"] == "device_trace"
        assert os.path.exists(os.path.join(reg.bench_dir, "layer_metrics",
                                           name + ".py"))
        family = name in ("kernel.dropout_ms_per_step",
                          "kernel.softmax_ms_per_step")
        assert m.get("workloads") == (bert if family else None)
    assert {n for t in program_ops.FAMILIES.values() for n in t} >= {
        "dropout", "dropout_grad", "softmax_grad", "batch_norm_grad"}
    assert "softmax_with_cross_entropy" not in program_ops.FAMILIES["softmax"]
