"""Device time by Program op (fluid/device_stats.py, fluid/profiler.py):
the scope ``run_block_ops`` opens around every op, the op map read back from
an executable's optimized HLO, the executables remembered for it, and the
join of a profiler trace with the map.

The fixtures are a pair: ``data/op_map_fixture.hlo.txt`` (an executable's
text, TPU style) and ``data/op_map_trace.textproto`` (a trace whose ``XLA
Ops`` carry that text's instruction names), small enough to compute every
number by hand (microseconds below).
"""
import gc
import os
import types
import weakref

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, device_stats as ds, profiler, trace
from paddle_tpu.fluid.core import Scope, scope_guard

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e-6


def _read(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return f.read()


@pytest.fixture(scope="module")
def fixture_map():
    return ds.hlo_op_map(_read("op_map_fixture.hlo.txt"))


@pytest.fixture(scope="module")
def fixture_trace():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(_read("op_map_trace.textproto"))


@pytest.fixture
def clean_table():
    """An empty remembered table, and no executable served from the
    persistent compile cache: renaming a scope changes no cache key
    (metadata is not part of it), so a warm directory would hand back
    whatever names an older tree compiled with."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    ds._remembered.clear()
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    ds._remembered.clear()
    trace.disable()
    trace.reset_all()


def _tiny_program(width=24):
    """fc + dropout + layer_norm + fc under Adam."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 16])
        y = fluid.data("y", [-1, 1])
        h = fluid.layers.fc(x, width, act="relu")
        h = fluid.layers.dropout(h, 0.1)
        h = fluid.layers.layer_norm(h)
        out = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(out, y))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    return main, startup, loss


def _feed(n=8):
    r = np.random.RandomState(0)
    return {"x": r.rand(n, 16).astype("float32"),
            "y": r.rand(n, 1).astype("float32")}


def _run_tiny(exe, width=24, steps=1):
    main, startup, loss = _tiny_program(width)
    exe.run(startup)
    for _ in range(steps):
        exe.run(main, feed=_feed(), fetch_list=[loss])
    return main


# ---------------------------------------------------------------------------
# the scope: one format, its writer and its parser
# ---------------------------------------------------------------------------

def _op(type_, inputs=None, outputs=None, attrs=None):
    outputs = outputs or {}
    return types.SimpleNamespace(
        type=type_, inputs=inputs or {}, outputs=outputs, attrs=attrs or {},
        output_arg_names=[n for v in outputs.values() for n in v])


@pytest.mark.parametrize("op, after_backward, want", [
    (_op("mul", {"X": ["a"]}, {"Out": ["fc_0.tmp_0"]}), False,
     ("mul", "forward", "fc_0.tmp_0")),
    # every backward op is one op type: the forward type names it
    (_op("generic_grad", {"G_Out": ["g"]}, {"GI_Y": ["fc_3.w_0@GRAD"]},
         {"fwd_type": "mul", "op_role": 1}), False,
     ("mul_grad", "backward", "fc_3.w_0.GRAD")),
    (_op("sum", {"X": ["a", "b"]}, {"Out": ["x@GRAD@RENAME_1"]},
         {"op_role": 1}), True, ("sum", "backward", "x.GRAD.RENAME_1")),
    # an op that takes Param and Grad, whatever its attributes say
    (_op("adam", {"Param": ["w"], "Grad": ["w@GRAD"]},
         {"ParamOut": ["w"]}), True, ("adam", "optimizer", "w")),
    # what follows the backward pass with no role of its own
    (_op("scale", {"X": ["w"]}, {"Out": ["scope/w.decay"]}), True,
     ("scale", "optimizer", "scope.w.decay")),
    (_op("scale", {"X": ["w"]}, {"Out": ["tmp_1"]}), False,
     ("scale", "forward", "tmp_1")),
    (_op("print", {"In": ["w"]}), False, ("print", "forward", "")),
])
def test_scope_round_trip(op, after_backward, want):
    name = ds.op_scope(op, after_backward)
    assert "@" not in name and "/" not in name
    assert ds.parse_scope(name) == want
    # found anywhere in an op_name path, under any wrapper
    for path in (f"jit(fn)/{name}/dot_general",
                 f"jit(constrained)/jit(fn)/{name}/transpose(jvp())/mul",
                 # a kernel that runs once per chip, and its backward
                 f"jit(constrained)/{name}/jit(body)/shard_map/pallas_call",
                 f"jit(constrained)/{name}/transpose(jvp(jit(body)))/"
                 f"shard_map/pallas_call",
                 f"jit(constrained)/{name}/transpose({name})/jvp()/"
                 f"shard_map/jit(_fused_attention_jit)/pallas_call",
                 f"jit(fn)/{name}"):
        assert ds.parse_scope(path) == want


def test_scope_format_is_pinned():
    assert ds.scope_name("mul_grad", "b", "fc_3.w_0@GRAD") \
        == "pd:b:mul_grad:fc_3.w_0.GRAD"
    assert ds.scope_name("layer_norm", "forward", "layer_norm_24.tmp_2") \
        == "pd:f:layer_norm:layer_norm_24.tmp_2"


def test_the_innermost_scope_is_the_ops():
    path = ("jit(fn)/pd:f:while:out_0/while/body/pd:b:mul_grad:w.GRAD/"
            "transpose(jvp())/dot_general")
    assert ds.parse_scope(path) == ("mul_grad", "backward", "w.GRAD")


@pytest.mark.parametrize("path", ["", None, "jit(fn)/mul/dot_general",
                                  "jit(fn)/jit(_threefry_seed)/shift_left"])
def test_a_path_without_a_scope_parses_to_none(path):
    assert ds.parse_scope(path) is None


# ---------------------------------------------------------------------------
# hlo_op_map on the stored text
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("instruction, want", [
    # the dW matmul with the Adam update fused in: the matmul is the hero
    ("divide_subtract_fusion.1",
     {"label": "mul_grad", "role": "backward", "instance": "fc_0.w_0.GRAD",
      "opcode": "fusion", "mxu": True, "also": ["adam"]}),
    # a fusion without metadata of its own: what most of its body carries
    ("fusion.1",
     {"label": "layer_norm", "role": "forward",
      "instance": "layer_norm_0.tmp_2", "opcode": "fusion", "mxu": False,
      "also": ["dropout"]}),
    # a Mosaic kernel: a custom call with its own metadata
    ("dropout.7",
     {"label": "dropout", "role": "forward", "instance": "dropout_0.tmp_0",
      "opcode": "custom-call", "mxu": False, "also": []}),
    # Mosaic kernels under a partitioned program's shard_map frame, as
    # the data-parallel BERT step names them (compiled for v5e:2x2)
    ("attention.4",
     {"label": "fused_multihead_attention", "role": "forward",
      "instance": "matmul_1.tmp_0", "opcode": "custom-call", "mxu": False,
      "also": []}),
    ("dropout_grad.5",
     {"label": "dropout_grad", "role": "backward",
      "instance": "fc_7.tmp_0.GRAD", "opcode": "custom-call", "mxu": False,
      "also": []}),
    ("convolution.8",
     {"label": "mul", "role": "forward", "instance": "fc_0.tmp_0",
      "opcode": "convolution", "mxu": True, "also": []}),
    # inside the while's body: the inner scope, not the while's
    ("multiply_add_fusion.2",
     {"label": "scale", "role": "forward", "instance": "tmp_3",
      "opcode": "fusion", "mxu": False, "also": []}),
    ("all-reduce.1",
     {"label": "c_allreduce_sum", "role": "backward",
      "instance": "fc_0.w_0.GRAD", "opcode": "all-reduce", "mxu": False,
      "also": []}),
    ("while.1",
     {"label": "while", "role": "forward", "instance": "out_0",
      "opcode": "while", "mxu": False, "also": []}),
    # the while's condition and a conditional's branch are mapped too
    ("compare.28",
     {"label": "less_than", "role": "forward", "instance": "cond_0",
      "opcode": "compare", "mxu": False, "also": []}),
    ("negate.30",
     {"label": "scale", "role": "forward", "instance": "tmp_5",
      "opcode": "negate", "mxu": False, "also": []}),
    ("multiply_subtract_fusion.5",
     {"label": "adam", "role": "optimizer", "instance": "fc_0.w_0",
      "opcode": "fusion", "mxu": False, "also": []}),
    # no scope anywhere: a parameter's copy, the PRNG key's arithmetic
    ("copy.9", None),
    ("custom-call.3", None),
    ("copy.32", None),
])
def test_hlo_op_map_on_the_fixture(fixture_map, instruction, want):
    assert fixture_map[instruction] == want


def test_hlo_op_map_covers_entry_and_control_flow_only(fixture_map):
    # what a fusion or a reduction calls is charged to its caller
    for inner in ("convolution.4", "multiply.13", "add.3", "multiply.18"):
        assert inner not in fixture_map
    assert ds.hlo_module_name(_read("op_map_fixture.hlo.txt")) == "jit_fn"
    assert ds.hlo_op_map("") == {}


# ---------------------------------------------------------------------------
# a Program through fluid.Executor on the CPU
# ---------------------------------------------------------------------------

def test_executor_map_names_every_op(clean_table):
    exe = fluid.Executor()
    _run_tiny(exe)
    exe.close()                      # the readers run after close()
    maps = ds.op_maps()
    assert [m["module"] for m in maps] == ["jit_fn", "jit_fn"]
    ops = [v for v in maps[-1]["map"].values() if v]
    labels = {v["label"] for v in ops}
    assert "generic_grad" not in labels
    assert not any(a == "generic_grad" for v in ops for a in v["also"])
    assert {"mul", "mul_grad", "layer_norm", "layer_norm_grad", "dropout",
            "adam"} <= labels
    assert {v["role"] for v in ops} == {"forward", "backward", "optimizer"}
    assert {v["role"] for v in ops if v["label"] == "adam"} == {"optimizer"}
    assert {v["role"] for v in ops if v["label"].endswith("_grad")} \
        == {"backward"}
    # instances separate one fc from the other
    assert len({v["instance"] for v in ops if v["label"] == "mul"}) == 2
    # a fusion with a dot inside is the matmul's, the rest is in `also`
    fused = [v for v in ops if v["opcode"] == "fusion" and v["mxu"]]
    assert fused and all(v["label"] in ("mul", "mul_grad") for v in fused)
    assert any(v["also"] for v in fused)
    assert all(v["mxu"] for v in ops if v["opcode"] == "dot")


def test_folded_amp_casts_are_inside_the_consumers_scope(clean_table):
    """passes/amp.py folds a cast into the op that reads it; the astype runs
    while run_block_ops gathers the op's inputs, and is the op's cost."""
    import re
    main, startup, loss = _tiny_program()
    bs = fluid.BuildStrategy()
    bs.amp = True
    exe = fluid.Executor()
    exe.run(startup)
    exe.run(fluid.CompiledProgram(main, build_strategy=bs), feed=_feed(),
            fetch_list=[loss])
    entry = list(ds._remembered.values())[-1]
    text = ds._aot_compile(entry["jitted"], entry["examples"]).as_text()
    casts = re.findall(r'op_name="([^"]*convert_element_type)"', text)
    assert casts and all(ds.parse_scope(c) for c in casts), \
        [c for c in casts if not ds.parse_scope(c)]


def test_control_flow_ops_get_the_scope(clean_table):
    from paddle_tpu.fluid.layers.control_flow import while_loop
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        i = fluid.layers.fill_constant([1], "float32", 0.0)
        n = fluid.layers.fill_constant([1], "float32", 3.0)
        (i,) = while_loop(lambda i: fluid.layers.less_than(i, n),
                          lambda i: fluid.layers.increment(i, 1.0), [i])
    exe = fluid.Executor()
    exe.run(startup)
    assert float(exe.run(main, fetch_list=[i])[0][0]) == 3.0
    ops = [v for v in ds.op_maps()[-1]["map"].values() if v]
    # the loop and, inside its body, the op of the sub-block
    assert any(v["opcode"] == "while" and v["label"] == "while"
               for v in ops)
    assert {"while", "increment"} <= {v["label"] for v in ops}


# ---------------------------------------------------------------------------
# the remembered executables
# ---------------------------------------------------------------------------

def test_remembered_table_survives_close_and_holds_no_buffer(clean_table):
    scope = Scope()
    exe = fluid.Executor()
    with scope_guard(scope):
        main = _run_tiny(exe)
    state = [weakref.ref(scope.find_var(p.name))
             for p in main.all_parameters()]
    assert state and all(r() is not None for r in state)
    exe.close()
    assert len(ds._remembered) == 2          # startup and step
    entry = list(ds._remembered.values())[-1]
    assert entry["map"] is None and entry["jitted"] is not None
    leaves = jax.tree_util.tree_leaves(entry["examples"])
    assert leaves and all(isinstance(a, jax.ShapeDtypeStruct)
                          for a in leaves)
    # drop the scope: its arrays are freed while the entry lives
    del scope
    gc.collect()
    assert all(r() is None for r in state)
    maps = ds.op_maps()                      # lowers and compiles now
    assert len(maps) == 2 and maps[-1]["map"]
    # ... and keeps only the parsed map
    assert entry["jitted"] is None and entry["examples"] is None
    assert ds.op_maps()[-1]["map"] is maps[-1]["map"]


def test_remembered_table_is_bounded(clean_table):
    f = jax.jit(lambda x: x + 1)
    for k in range(ds._REMEMBERED_MAX + 3):
        ds.remember(("k", k), f, (np.zeros(2, "float32"),))
    assert list(ds._remembered) == [("k", k) for k in range(3, 11)]
    ds.remember(("k", 5), f, (np.zeros(2, "float32"),))   # newest again
    assert list(ds._remembered)[-1] == ("k", 5)
    ds.remember(("no", "lower"), lambda x: x, (1,))       # not lowerable
    assert ("no", "lower") not in ds._remembered
    ds.forget(("k", 5))
    ds.forget(("k", 5))
    assert ("k", 5) not in ds._remembered


def test_eviction_retires_the_remembered_entry(clean_table):
    old = core.get_flag("executor_cache_capacity", 128)
    core.set_flags({"FLAGS_executor_cache_capacity": 1})
    try:
        exe = fluid.Executor()
        _run_tiny(exe)               # the startup entry is evicted by
        labels = [e["label"] for e in ds._remembered.values()]
        assert len(labels) == 1      # the step's
        _run_tiny(exe, width=40)     # and so on
        assert len(ds._remembered) == 1
        assert [e["label"] for e in ds._remembered.values()] != labels
    finally:
        core.set_flags({"FLAGS_executor_cache_capacity": old})


def test_capture_fills_the_map_from_its_own_compile(clean_table,
                                                     monkeypatch):
    core.set_flags({"FLAGS_device_cost_analysis": True})
    try:
        exe = fluid.Executor()
        _run_tiny(exe)
    finally:
        core.set_flags({"FLAGS_device_cost_analysis": "auto"})
    assert all(e["map"] is not None and e["jitted"] is None
               for e in ds._remembered.values())
    # no third compile when a reader asks
    monkeypatch.setattr(ds, "_aot_compile", lambda *a: 1 / 0)
    maps = ds.op_maps()
    assert len(maps) == 2 and "adam" in {
        v["label"] for v in maps[-1]["map"].values() if v}


def test_sds_tree_keeps_a_committed_sharding():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    sharded = jax.device_put(np.ones((8, 4), "float32"),
                             NamedSharding(mesh, P("dp")))
    tree = ds.sds_tree({"a": sharded, "b": np.ones(3, "float32"),
                        "c": jax.numpy.ones(2)})
    assert tree["a"].sharding == sharded.sharding
    assert tree["b"].sharding is None and tree["c"].sharding is None
    assert ds.sds_tree(tree, shardings=False)["a"].sharding is None
    # so the AOT lowering of a partitioned program is the one that ran
    f = jax.jit(lambda a: a.sum())
    assert "all-reduce" in ds._aot_compile(f, [tree["a"]]).as_text()
    # a jit with in_shardings of its own refuses an argument committed
    # elsewhere: its own decide
    one = jax.device_put(np.ones((8, 4), "float32"), jax.devices()[0])
    g = jax.jit(lambda a: a.sum(),
                in_shardings=(NamedSharding(mesh, P("dp")),))
    assert "all-reduce" in ds._aot_compile(g, ds.sds_tree([one])).as_text()
    # better: the structs placed where the step really receives them
    placed = ds.sds_tree(({"a": one}, np.ones(2, "float32")),
                         shardings=({"a": sharded.sharding}, None))
    assert placed[0]["a"].sharding == sharded.sharding
    assert placed[1].sharding is None


def test_a_plan_wrapped_step_is_remembered_as_it_ran(clean_table):
    """The structs of a partitioned step sit on the plan's in_shardings, so
    asking for its map re-traces nothing: lower() finds the very trace,
    lowering and executable of the call that ran."""
    main, startup, loss = _tiny_program()
    bs = fluid.BuildStrategy()
    bs.sharding = "dp"
    exe = fluid.Executor()
    exe.run(startup)
    exe.run(fluid.CompiledProgram(main, build_strategy=bs), feed=_feed(),
            fetch_list=[loss])
    entry = list(ds._remembered.values())[-1]
    leaves = jax.tree_util.tree_leaves(entry["examples"])
    assert leaves and all(a.sharding is not None for a in leaves)
    dispatched = trace.metrics().counter("executor.ops_dispatched")
    before = dispatched.value
    ops = [v for v in ds.op_maps()[-1]["map"].values() if v]
    assert dispatched.value == before            # no op was lowered again
    assert {"mul_grad", "adam"} <= {v["label"] for v in ops}


# ---------------------------------------------------------------------------
# the join with a trace
# ---------------------------------------------------------------------------

def _maps(fixture_map):
    # two executables of one module name (the benchmark's step and its
    # reference check are both jit_fn): the one that covers the time wins
    return [{"label": "check", "module": "jit_fn",
             "map": {"fusion.1": None, "fusion.77": None}},
            {"label": "step", "module": "jit_fn", "map": fixture_map}]


def test_device_time_by_op_totals(fixture_map, fixture_trace):
    t = ds.device_time_by_op(fixture_trace, maps=_maps(fixture_map))
    assert t["devices"] == 2
    # device 0: 830 us in two runs of the step and 2 x 6 us in the PRNG's
    # program; device 1: 500 us.  Means over the devices.
    assert t["busy_s"] == pytest.approx((842 + 500) / 2 * US)
    # all but %copy.9 (50 us, no scope) and the PRNG program's %fusion.1,
    # which shares its name with the step's and must not take its label
    assert t["attributed_s"] == pytest.approx((780 + 500) / 2 * US)
    assert t["mxu_s"] == pytest.approx((250 + 200) / 2 * US)
    assert t["steps"] == pytest.approx(1.5)
    assert t["roles"] == pytest.approx({
        "forward": (100 + 40 + 80 + 180 + 50 + 300) / 2 * US,
        "backward": (80 + 200 + 200) / 2 * US,
        "optimizer": 50 / 2 * US})
    assert sum(t["roles"].values()) == pytest.approx(t["attributed_s"])
    # by class: the name without its number
    assert [(u["module"], u["instruction"], u["instructions"])
            for u in t["unattributed"]] \
        == [("jit_fn", "copy", 1), ("jit__threefry_fold_in", "fusion", 1)]
    step, other = t["matched"]
    assert step["executable"] == "step" and step["runs"] == 1.5
    assert step["covered_s"] == pytest.approx(step["busy_s"])
    assert other["module"] == "jit__threefry_fold_in" \
        and other["executable"] is None


def test_device_time_by_op_rows(fixture_map, fixture_trace):
    t = ds.device_time_by_op(fixture_trace, maps=_maps(fixture_map))
    rows = {(r["label"], r["role"]): r for r in t["labels"]}
    assert [r["seconds"] for r in t["labels"]] \
        == sorted((r["seconds"] for r in t["labels"]), reverse=True)
    dw = rows["mul_grad", "backward"]
    assert dw["seconds"] == pytest.approx(200 * US) and dw["also"] == ["adam"]
    assert dw["mxu_s"] == pytest.approx(dw["seconds"])
    assert dw["calls"] == 1 and dw["instructions"] == 1
    # the while's own time is what its children leave: 200 - 80 - 80
    assert rows["while", "forward"]["seconds"] == pytest.approx(20 * US)
    drop = rows["dropout", "forward"]
    assert drop["seconds"] == pytest.approx(90 * US)
    assert (drop["min_s"], drop["max_s"]) == pytest.approx((80 * US,
                                                            100 * US))
    assert rows["layer_norm", "forward"]["also"] == ["dropout"]
    inst = {(r["label"], r["instance"]): r["seconds"]
            for r in t["instances"]}
    assert inst["scale", "tmp_3"] == pytest.approx(40 * US)
    assert inst["adam", "fc_0.w_0"] == pytest.approx(25 * US)


@pytest.mark.parametrize("events, want", [
    # nested: the parent's time less its children's
    ([("p", 0, 100), ("a", 10, 20), ("b", 20, 50)],
     {"p": 60, "a": 10, "b": 30}),
    # a successor stamped a little early is no child: the overlap stays
    # the predecessor's, and the self times add up to the union
    ([("a", 0, 10), ("b", 9, 30)], {"a": 10, "b": 20}),
    ([("p", 0, 100), ("a", 10, 20), ("b", 19, 30), ("c", 100, 110)],
     {"p": 80, "a": 10, "b": 10, "c": 10}),
    ([("a", 0, 10), ("b", 0, 10)], {"a": 0, "b": 10}),
])
def test_self_times_add_up_to_the_union(events, want):
    got = {}
    for key, t in ds._self_times(events):
        got[key] = got.get(key, 0) + t
    assert got == want


def test_device_time_by_op_window(fixture_map, fixture_trace):
    # the first run of the step alone, cut in the middle of its last op
    t = ds.device_time_by_op(fixture_trace, window=(90 * US, 460 * US),
                             maps=_maps(fixture_map))
    rows = {r["label"]: r["seconds"] for r in t["labels"]}
    assert rows["dropout"] == pytest.approx(40 / 2 * US)
    assert rows["layer_norm"] == pytest.approx((100 + 300) / 2 * US)
    assert "adam" not in rows
    assert t["steps"] == 1.0
    assert t["busy_s"] == pytest.approx(t["attributed_s"])


@pytest.mark.parametrize("maps, executable", [
    # no map at all: everything unattributed
    ([], None),
    # a runtime that names its programs otherwise: coverage alone decides
    ([{"label": "step", "module": "jit_step", "map": None}], "step"),
])
def test_device_time_by_op_matching(fixture_map, fixture_trace, maps,
                                    executable):
    maps = [dict(m, map=fixture_map) for m in maps]
    t = ds.device_time_by_op(fixture_trace, maps=maps)
    assert t["matched"][0]["executable"] == executable
    assert (t["attributed_s"] > 0) == bool(executable)
    assert t["busy_s"] == pytest.approx((842 + 500) / 2 * US)


def test_a_trace_without_device_ops_gives_none(fixture_map):
    from jax.profiler import ProfileData
    host_only = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "main" '
        'events { metadata_id: 1 offset_ps: 0 duration_ps: 5 } } '
        'event_metadata { key: 1 value { id: 1 name: "x" } } }')
    assert ds.device_time_by_op(host_only, maps=_maps(fixture_map)) is None


# ---------------------------------------------------------------------------
# what an operator gets: fluid.profiler's two tables
# ---------------------------------------------------------------------------

def _write_xplane(profile_path):
    from jax.profiler import ProfileData
    d = os.path.join(profile_path, "plugins", "profile", "2026_09_28")
    os.makedirs(d)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            _read("op_map_trace.textproto")))


@pytest.mark.parametrize("sorted_key, first", [
    (None, "layer_norm"), ("total", "layer_norm"), ("calls", "layer_norm"),
    ("max", "layer_norm"), ("min", "mul_grad"), ("ave", "layer_norm")])
def test_device_table_is_sorted_by_the_key(fixture_map, fixture_trace,
                                           sorted_key, first):
    t = ds.device_time_by_op(fixture_trace, maps=_maps(fixture_map))
    text = ds.format_device_ops(t, sorted_key)
    rows = text.splitlines()
    assert "Device time by Program op" in rows[0]
    body = rows[rows.index(next(r for r in rows
                                if r.startswith("Program op"))) + 1:]
    assert body[0].split()[0] == first
    assert body[-1].startswith("(no Program op)")
    dw = next(r for r in body if r.startswith("mul_grad")).split()
    # Program op, role, calls, total ms, ms per step (1.5 steps), busy %, also
    assert dw == ["mul_grad", "backward", "1", "0.200", "0.133", "29.8",
                  "adam"]


def test_profiler_prints_device_time_by_program_op(
        clean_table, monkeypatch, tmp_path, capsys, fixture_map):
    """Around Executor steps, where the device trace holds XLA Ops."""
    started = {}

    def start_trace(path, profiler_options=None, **kw):
        started["options"] = profiler_options
        _write_xplane(path)          # what a TPU's profiler would leave

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(ds, "op_maps", lambda: _maps(fixture_map))
    with profiler.profiler(sorted_key="total", profile_path=str(tmp_path)):
        _run_tiny(fluid.Executor(), steps=2)
    out = capsys.readouterr().out
    device = out.index("Device time by Program op")
    host = out.index(profiler.HOST_TABLE_TITLE)
    assert device < host             # the device's table first
    assert "mul_grad" in out[device:host] and "adam" in out[device:host]
    assert "executor::step" in out[host:]
    # the device's half of the profiler alone
    assert started["options"].host_tracer_level == 0
    assert started["options"].python_tracer_level == 0


def test_profiler_prints_only_the_host_table_without_device_planes(
        clean_table, tmp_path, capsys):
    with profiler.profiler(profile_path=str(tmp_path)):
        _run_tiny(fluid.Executor(), steps=2)
    out = capsys.readouterr().out
    assert "Device time by Program op" not in out
    assert profiler.HOST_TABLE_TITLE in out and "executor::step" in out
    assert profiler.device_op_table(str(tmp_path / "nothing")) is None


def test_host_timeline_carries_the_wall_clock_of_its_epoch(tmp_path):
    import json
    import time
    trace.enable()
    try:
        wall = time.time_ns()
        t0 = trace.now()
        trace.complete("x", t0, cat="step")
        path = trace.export_chrome_trace(str(tmp_path / "t.json"))
    finally:
        trace.disable()
        trace.reset_all()
    with open(path) as f:
        doc = json.load(f)
    meta = doc["metadata"]
    assert meta["epoch_unix_ns"] == pytest.approx(meta["epoch_unix_ts"] * 1e9)
    ev = next(e for e in doc["traceEvents"] if e["name"] == "x")
    # epoch + ts is the wall clock the event was stamped at (the two clocks
    # drift by less than a millisecond over a test run)
    assert meta["epoch_unix_ns"] + ev["ts"] * 1e3 \
        == pytest.approx(wall, abs=50e6)


def test_hlo_op_map_reads_instructions_printed_over_several_lines():
    """A Mosaic call that takes a literal (the splash kernel's block masks)
    is printed over several lines, the last of which starts with ``}}`` at
    column 0 and carries the metadata: it neither ends the computation nor
    loses its scope."""
    text = """HloModule jit_fn, is_scheduled=true

ENTRY %main.1 (p0: bf16[8,128]) -> bf16[8,128] {
  %p0 = bf16[8,128]{1,0} parameter(0)
  %splash_mha_fwd.1 = bf16[8,128]{1,0} custom-call(%p0), custom_call_target="tpu_custom_call", literal={ {
    { 1, 0 },
    { 1, 1 }
}}, metadata={op_name="jit(fn)/pd:f:fused_multihead_attention:attn_0.tmp_0/vmap(jit(_splash_attention))/pallas_call"}
  ROOT %add.2 = bf16[8,128]{1,0} add(%splash_mha_fwd.1, %p0), metadata={op_name="jit(fn)/pd:f:elementwise_add:tmp_3/add"}
}
"""
    got = ds.hlo_op_map(text)
    assert got["splash_mha_fwd.1"]["label"] == "fused_multihead_attention"
    assert got["splash_mha_fwd.1"]["instance"] == "attn_0.tmp_0"
    assert got["add.2"]["label"] == "elementwise_add"
