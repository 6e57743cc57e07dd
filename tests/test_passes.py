"""Unit tests for the Program-IR pass framework (fluid/passes/): registry,
pattern matcher, pipeline enforcement, and per-pass semantics."""
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace
from paddle_tpu.fluid.framework import Program, reset_unique_name
from paddle_tpu.fluid.passes import (Pass, PassContext, PassPipeline,
                                     Pattern, create_pass, get_pass_names,
                                     register_pass, program_to_dot,
                                     passes_for_build_strategy)
from paddle_tpu.fluid.passes.core import _registry


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_builtin_catalog_registered():
    names = get_pass_names()
    for want in ("dce", "constant_fold", "fuse_elewise_add_act",
                 "fuse_bn_act", "coalesce_allreduce", "prune_identity",
                 "memory_optimize_legacy"):
        assert want in names, names


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        @register_pass
        class Dup(Pass):
            name = "dce"


def test_unknown_pass_rejected():
    with pytest.raises(KeyError, match="no pass named"):
        create_pass("no_such_pass")


def test_custom_pass_runs_in_pipeline():
    class CountOps(Pass):
        name = "count_ops_test"
        writes = frozenset()

        def apply(self, program, ctx):
            return {"ops_seen": sum(len(b.ops) for b in program.blocks)}

    try:
        register_pass(CountOps)
        p = Program()
        b = p.global_block()
        b.create_var(name="x", shape=[2], dtype="float32")
        b.append_op("scale", {"X": ["x"]}, {"Out": ["y"]}, {"scale": 2.0})
        stats = PassPipeline([create_pass("count_ops_test")]).apply(p)
        assert stats["count_ops_test"]["ops_seen"] == 1
        assert trace.metrics().counter(
            "pass.count_ops_test.ops_seen").value >= 1
    finally:
        _registry._passes.pop("count_ops_test", None)


def test_invalid_aspect_rejected():
    class BadAspect(Pass):
        name = "bad_aspect_test"
        writes = frozenset({"kernels"})

    with pytest.raises(ValueError, match="unknown IR aspects"):
        BadAspect()


# ---------------------------------------------------------------------------
# pipeline contract enforcement
# ---------------------------------------------------------------------------

def _two_op_program():
    p = Program()
    b = p.global_block()
    b.create_var(name="x", shape=[4], dtype="float32")
    b.append_op("scale", {"X": ["x"]}, {"Out": ["y"]}, {"scale": 2.0})
    b.append_op("scale", {"X": ["y"]}, {"Out": ["z"]}, {"scale": 3.0})
    return p, b


def test_pipeline_rejects_unbumped_mutation():
    class SneakyDrop(Pass):
        name = "sneaky_drop_test"

        def apply(self, program, ctx):
            program.global_block().ops.pop()     # bare surgery: no bump
            return {}

    p, _ = _two_op_program()
    with pytest.raises(RuntimeError, match="without bumping"):
        PassPipeline([SneakyDrop()]).apply(p)


def test_pipeline_rejects_readonly_pass_that_mutates():
    class LyingReadOnly(Pass):
        name = "lying_readonly_test"
        writes = frozenset()

        def apply(self, program, ctx):
            program.global_block()._remove_op(0)
            return {}

    p, _ = _two_op_program()
    with pytest.raises(RuntimeError, match="empty write set"):
        PassPipeline([LyingReadOnly()]).apply(p)


def test_pass_spans_and_counters_emitted():
    trace.reset_all()
    trace.enable()
    try:
        p, _ = _two_op_program()
        PassPipeline([create_pass("dce")]).apply(p, targets=["z"])
        names = [e["name"] for e in trace.get_events()]
        assert "pass::dce" in names
    finally:
        trace.disable()
        trace.reset_all()


# ---------------------------------------------------------------------------
# pattern matcher
# ---------------------------------------------------------------------------

def test_pattern_var_capture_and_order():
    p, b = _two_op_program()
    pat = Pattern("scale_chain")
    x, y, z = pat.vars("x y z")
    pat.op("scale", ins={"X": [x]}, outs={"Out": [y]})
    pat.op("scale", ins={"X": [y]}, outs={"Out": [z]})
    m = pat.first_match(b)
    assert m is not None
    assert m.var("x") == "x" and m.var("y") == "y" and m.var("z") == "z"
    assert [op.type for op in m.ops] == ["scale", "scale"]


def test_pattern_capture_consistency_rejects():
    p = Program()
    b = p.global_block()
    b.create_var(name="a", shape=[2], dtype="float32")
    b.create_var(name="c", shape=[2], dtype="float32")
    b.append_op("scale", {"X": ["a"]}, {"Out": ["b"]}, {})
    b.append_op("scale", {"X": ["c"]}, {"Out": ["d"]}, {})  # not chained
    pat = Pattern("chain")
    x, y, z = pat.vars("x y z")
    pat.op("scale", ins={"X": [x]}, outs={"Out": [y]})
    pat.op("scale", ins={"X": [y]}, outs={"Out": [z]})
    assert pat.first_match(b) is None


def test_pattern_attr_predicate_and_alternatives():
    p, b = _two_op_program()
    pat = Pattern("big_scale")
    pat.op(("scale", "cast"), attrs={"scale": lambda v: v and v > 2.5})
    ms = pat.match_all(b)
    assert len(ms) == 1 and ms[0].ops[0].attrs["scale"] == 3.0


def test_match_all_non_overlapping():
    p = Program()
    b = p.global_block()
    b.create_var(name="v0", shape=[2], dtype="float32")
    for i in range(4):
        b.append_op("scale", {"X": [f"v{i}"]}, {"Out": [f"v{i+1}"]}, {})
    pat = Pattern("pair")
    x, y, z = pat.vars("x y z")
    pat.op("scale", ins={"X": [x]}, outs={"Out": [y]})
    pat.op("scale", ins={"X": [y]}, outs={"Out": [z]})
    assert len(pat.match_all(b)) == 2      # 4 ops -> 2 disjoint pairs


# ---------------------------------------------------------------------------
# constant folding
# ---------------------------------------------------------------------------

def test_constant_fold_scale_of_fill():
    p = Program()
    b = p.global_block()
    b.append_op("fill_constant", {}, {"Out": ["c"]},
                {"shape": [3], "value": 2.0, "dtype": "float32"})
    b.append_op("scale", {"X": ["c"]}, {"Out": ["d"]},
                {"scale": 3.0, "bias": 1.0})
    PassPipeline([create_pass("constant_fold"),
                  create_pass("dce")]).apply(p, targets=["d"])
    assert [op.type for op in b.ops] == ["fill_constant"]
    assert b.ops[0].attrs["value"] == pytest.approx(7.0)
    d, = fluid.Executor().run(p, fetch_list=["d"])
    assert np.allclose(d, 7.0)


def test_constant_fold_cast_of_fill():
    p = Program()
    b = p.global_block()
    b.append_op("fill_constant", {}, {"Out": ["c"]},
                {"shape": [2], "value": 5.0, "dtype": "float32"})
    b.append_op("cast", {"X": ["c"]}, {"Out": ["d"]},
                {"out_dtype": "int32"})
    PassPipeline([create_pass("constant_fold"),
                  create_pass("dce")]).apply(p, targets=["d"])
    assert [op.type for op in b.ops] == ["fill_constant"]
    d, = fluid.Executor().run(p, fetch_list=["d"])
    assert d.dtype == np.int32 and np.all(d == 5)


def test_constant_fold_composes_scale_chain():
    p = Program()
    b = p.global_block()
    b.create_var(name="x", shape=[3], dtype="float32", is_data=True)
    b.append_op("scale", {"X": ["x"]}, {"Out": ["y"]},
                {"scale": 2.0, "bias": 1.0})
    b.append_op("scale", {"X": ["y"]}, {"Out": ["z"]},
                {"scale": 3.0, "bias": 0.5})
    PassPipeline([create_pass("constant_fold"),
                  create_pass("dce")]).apply(p, targets=["z"])
    assert [op.type for op in b.ops] == ["scale"]
    z, = fluid.Executor().run(p, feed={"x": np.ones(3, "float32")},
                              fetch_list=["z"])
    assert np.allclose(z, (1.0 * 2.0 + 1.0) * 3.0 + 0.5)


def test_constant_fold_compose_blocked_by_inplace_rewrite():
    """Rewiring the outer scale through the inner's input is unsound when
    that input is rewritten in between — the fold must not fire."""
    p = Program()
    b = p.global_block()
    b.create_var(name="x", shape=[3], dtype="float32", is_data=True)
    b.append_op("scale", {"X": ["x"]}, {"Out": ["y"]}, {"scale": 2.0})
    b.append_op("scale", {"X": ["x"]}, {"Out": ["x"]}, {"scale": 0.0})
    b.append_op("scale", {"X": ["y"]}, {"Out": ["z"]}, {"scale": 3.0})
    PassPipeline([create_pass("constant_fold")]).apply(p, targets=["z"])
    z, = fluid.Executor().run(p, feed={"x": np.ones(3, "float32")},
                              fetch_list=["z"])
    assert np.allclose(z, 6.0), z     # not 0.0: fold must have been skipped


# ---------------------------------------------------------------------------
# identity pruning
# ---------------------------------------------------------------------------

def test_prune_identity_scale_one():
    p = Program()
    b = p.global_block()
    b.create_var(name="x", shape=[3], dtype="float32", is_data=True)
    b.append_op("scale", {"X": ["x"]}, {"Out": ["y"]},
                {"scale": 1.0, "bias": 0.0})
    b.append_op("scale", {"X": ["y"]}, {"Out": ["z"]}, {"scale": 2.0})
    PassPipeline([create_pass("prune_identity")]).apply(p, targets=["z"])
    assert [op.type for op in b.ops] == ["scale"]
    assert b.ops[0].inputs["X"] == ["x"]     # consumer rewired
    z, = fluid.Executor().run(p, feed={"x": np.ones(3, "float32")},
                              fetch_list=["z"])
    assert np.allclose(z, 2.0)


def test_prune_identity_protects_fetch_target():
    p = Program()
    b = p.global_block()
    b.create_var(name="x", shape=[3], dtype="float32", is_data=True)
    b.append_op("scale", {"X": ["x"]}, {"Out": ["y"]},
                {"scale": 1.0, "bias": 0.0})
    PassPipeline([create_pass("prune_identity")]).apply(p, targets=["y"])
    assert [op.type for op in b.ops] == ["scale"]   # y is fetched: kept
    y, = fluid.Executor().run(p, feed={"x": np.ones(3, "float32")},
                              fetch_list=["y"])
    assert np.allclose(y, 1.0)


def test_prune_identity_keeps_persistable_assign_snapshot():
    """assign-of-persistable is the data_norm snapshot idiom (read the
    OLD value before an in-place state update) — must survive."""
    p = Program()
    b = p.global_block()
    b.create_parameter(name="state", shape=[3], dtype="float32")
    b.append_op("assign", {"X": ["state"]}, {"Out": ["snap"]}, {})
    b.append_op("scale", {"X": ["snap"]}, {"Out": ["z"]}, {"scale": 2.0})
    PassPipeline([create_pass("prune_identity")]).apply(p, targets=["z"])
    assert [op.type for op in b.ops] == ["assign", "scale"]


# ---------------------------------------------------------------------------
# DCE
# ---------------------------------------------------------------------------

def test_dce_removes_dead_branch_keeps_state_writes():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        y = fluid.data("y", [-1, 1])
        h = fluid.layers.fc(x, 4, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        dead = fluid.layers.scale(h, scale=5.0)        # never fetched
        dead2 = fluid.layers.mean(dead)                # noqa: F841
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    n0 = len(main.global_block().ops)
    stats = PassPipeline([create_pass("dce")]).apply(
        main, targets=[loss.name])
    assert stats["dce"]["ops_removed"] >= 2
    types = [op.type for op in main.global_block().ops]
    assert "sgd" in types                   # optimizer state writes kept
    assert len(types) < n0
    exe = fluid.Executor()
    exe.run(startup)
    lv, = exe.run(main, feed={"x": np.ones((2, 4), "float32"),
                              "y": np.zeros((2, 1), "float32")},
                  fetch_list=[loss])
    assert np.isfinite(float(np.asarray(lv).ravel()[0]))


# ---------------------------------------------------------------------------
# fusion passes
# ---------------------------------------------------------------------------

def _count(block, t):
    return sum(1 for op in block.ops if op.type == t)


def test_fuse_add_act_forward_only():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        h = fluid.layers.fc(x, 8, act="relu")
        out = fluid.layers.reduce_sum(h)
    exe = fluid.Executor()
    exe.run(startup)
    feed = {"x": np.linspace(-1, 1, 8).reshape(2, 4).astype("float32")}
    ref, = exe.run(main, feed=feed, fetch_list=[out])
    PassPipeline([create_pass("fuse_elewise_add_act")]).apply(
        main, targets=[out.name])
    b = main.global_block()
    assert _count(b, "fused_elemwise_activation") == 1
    assert _count(b, "elementwise_add") == 0 and _count(b, "relu") == 0
    got, = exe.run(main, feed=feed, fetch_list=[out])
    assert np.allclose(ref, got, rtol=1e-6)


def test_fuse_add_act_training_fuses_grad_pair():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        y = fluid.data("y", [-1, 1])
        h = fluid.layers.fc(x, 8, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    b = main.global_block()
    grads0 = _count(b, "generic_grad")
    stats = PassPipeline([create_pass("fuse_elewise_add_act")]).apply(
        main, targets=[loss.name])
    assert stats["fuse_elewise_add_act"]["ops_fused"] == 1
    assert _count(b, "fused_elemwise_activation") == 1
    assert _count(b, "generic_grad") == grads0 - 1   # grad pair collapsed
    fused_grads = [op for op in b.ops if op.type == "generic_grad"
                   and op.attrs.get("fwd_type")
                   == "fused_elemwise_activation"]
    assert len(fused_grads) == 1


def test_fuse_add_act_skipped_when_intermediate_fetched():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        h = fluid.layers.fc(x, 8, act="relu")
    b = main.global_block()
    pre_act = [op for op in b.ops
               if op.type == "elementwise_add"][0].outputs["Out"][0]
    stats = PassPipeline([create_pass("fuse_elewise_add_act")]).apply(
        main, targets=[h.name, pre_act])
    assert stats["fuse_elewise_add_act"].get("ops_fused", 0) == 0
    assert _count(b, "elementwise_add") == 1     # protected: untouched


def test_fuse_bn_act_training_parity():
    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [-1, 3, 6, 6])
            y = fluid.data("y", [-1, 1], dtype="int64")
            c = fluid.layers.conv2d(x, 4, 3, padding=1, bias_attr=False)
            c = fluid.layers.batch_norm(c, act="relu")
            f = fluid.layers.reshape(c, [-1, 4 * 6 * 6])
            logits = fluid.layers.fc(f, 5, bias_attr=False)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    xs = rng.randn(4, 3, 6, 6).astype("float32")
    ys = rng.randint(0, 5, (4, 1)).astype("int64")

    reset_unique_name()
    m1, s1, l1 = build()
    exe1 = fluid.Executor()
    with fluid.scope_guard(fluid.core.Scope()):
        exe1.run(s1)
        ref = [exe1.run(m1, feed={"x": xs, "y": ys},
                        fetch_list=[l1])[0] for _ in range(3)]

    reset_unique_name()
    m2, s2, l2 = build()
    PassPipeline([create_pass("fuse_bn_act")]).apply(
        m2, targets=[l2.name])
    b = m2.global_block()
    assert _count(b, "fused_bn_activation") == 1
    assert _count(b, "batch_norm") == 0
    exe2 = fluid.Executor()
    with fluid.scope_guard(fluid.core.Scope()):
        exe2.run(s2)
        got = [exe2.run(m2, feed={"x": xs, "y": ys},
                        fetch_list=[l2])[0] for _ in range(3)]
    for a, c in zip(ref, got):
        assert np.allclose(a, c, rtol=1e-4, atol=1e-5), (a, c)


# ---------------------------------------------------------------------------
# allreduce coalescing
# ---------------------------------------------------------------------------

def _allreduce_program(n, ring_id=0):
    p = Program()
    b = p.global_block()
    for i in range(n):
        b.create_var(name=f"g{i}", shape=[4], dtype="float32",
                     is_data=True)
        b.append_op("c_allreduce_sum", {"X": [f"g{i}"]},
                    {"Out": [f"g{i}"]}, {"ring_id": ring_id, "op_role": 1})
    return p, b


@pytest.mark.parametrize("n,bucket", [(7, 3), (8, 4), (5, 32), (2, 2)])
def test_coalesce_launch_count(n, bucket):
    p, b = _allreduce_program(n)
    PassPipeline([create_pass("coalesce_allreduce",
                              bucket_size=bucket)]).apply(p)
    launches = sum(1 for op in b.ops
                   if op.type.startswith("c_allreduce"))
    assert launches == -(-n // bucket)       # ceil(n/bucket)


def test_coalesce_respects_ring_and_interruption():
    p, b = _allreduce_program(2)
    b.create_var(name="m", shape=[4], dtype="float32", is_data=True)
    b.append_op("scale", {"X": ["m"]}, {"Out": ["m2"]}, {"scale": 2.0})
    b.create_var(name="g9", shape=[4], dtype="float32", is_data=True)
    b.append_op("c_allreduce_sum", {"X": ["g9"]}, {"Out": ["g9"]},
                {"ring_id": 1, "op_role": 1})
    PassPipeline([create_pass("coalesce_allreduce",
                              bucket_size=8)]).apply(p)
    types = [op.type for op in b.ops]
    # first run (2 same-ring ops) coalesces; the ring-1 op after the scale
    # is alone -> untouched
    assert types == ["c_allreduce_coalesced", "scale", "c_allreduce_sum"]


def test_coalesce_never_reorders_interleaved_kinds():
    """A sum that reads another collective's output must stay AFTER it:
    only contiguous same-(type, ring) segments coalesce, in place."""
    p = Program()
    b = p.global_block()
    for n in ("a", "b"):
        b.create_var(name=n, shape=[4], dtype="float32", is_data=True)
    b.append_op("c_allreduce_sum", {"X": ["a"]}, {"Out": ["a"]},
                {"ring_id": 0})
    b.append_op("c_allreduce_avg", {"X": ["b"]}, {"Out": ["b"]},
                {"ring_id": 0})
    b.append_op("c_allreduce_sum", {"X": ["b"]}, {"Out": ["c"]},
                {"ring_id": 0})
    PassPipeline([create_pass("coalesce_allreduce",
                              bucket_size=8)]).apply(p)
    types = [op.type for op in b.ops]
    assert types == ["c_allreduce_sum", "c_allreduce_avg",
                     "c_allreduce_sum"], types   # untouched: no reorder


def test_coalesced_lowering_identity_single_replica():
    p, b = _allreduce_program(4)
    PassPipeline([create_pass("coalesce_allreduce",
                              bucket_size=4)]).apply(p)
    feeds = {f"g{i}": np.full((4,), float(i) + 1, "float32")
             for i in range(4)}
    outs = fluid.Executor().run(p, feed=feeds,
                                fetch_list=[f"g{i}" for i in range(4)])
    for i, o in enumerate(outs):
        assert np.allclose(o, float(i) + 1)


def test_fleet_insert_allreduce_then_coalesce():
    from paddle_tpu.distributed.fleet.meta_optimizers.common import \
        insert_allreduce_ops
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        y = fluid.data("y", [-1, 1])
        h = fluid.layers.fc(x, 8, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        opt = fluid.optimizer.SGDOptimizer(0.1)
        pgs = opt.backward(loss)
        insert_allreduce_ops(main.global_block(), pgs, ring_id=0,
                             average=True)
        opt.apply_gradients(pgs)
    b = main.global_block()
    n = _count(b, "c_allreduce_avg")
    assert n == len(pgs)
    bs = fluid.BuildStrategy()
    bs.fuse_all_reduce_ops = True
    bs.fuse_grad_size_in_num = 2
    cp = fluid.CompiledProgram(main, build_strategy=bs)
    exe = fluid.Executor()
    exe.run(startup)
    lv, = exe.run(cp, feed={"x": np.ones((2, 4), "float32"),
                            "y": np.zeros((2, 1), "float32")},
                  fetch_list=[loss])
    launches = sum(1 for op in b.ops
                   if op.type.startswith("c_allreduce"))
    assert launches <= -(-n // 2)
    assert np.isfinite(float(np.asarray(lv).ravel()[0]))


# ---------------------------------------------------------------------------
# graphviz + BuildStrategy wiring
# ---------------------------------------------------------------------------

def test_debug_graphviz_path_dumps_stages(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        h = fluid.layers.fc(x, 8, act="relu")
        out = fluid.layers.reduce_sum(h)
    bs = fluid.BuildStrategy()
    bs.fuse_elewise_add_act_ops = True
    bs.debug_graphviz_path = str(tmp_path / "gv")
    cp = fluid.CompiledProgram(main, build_strategy=bs)
    exe = fluid.Executor()
    exe.run(startup)
    exe.run(cp, feed={"x": np.ones((2, 4), "float32")}, fetch_list=[out])
    files = sorted(os.listdir(str(tmp_path / "gv")))
    assert files[0] == "00_input.dot"
    assert any("fuse_elewise_add_act" in f for f in files)
    body = open(str(tmp_path / "gv" / files[-1])).read()
    assert body.startswith("digraph") and "fused_elemwise_activation" in body


def test_program_to_dot_shapes_and_persistables():
    p, b = _two_op_program()
    b.create_parameter(name="w", shape=[4], dtype="float32")
    b.append_op("elementwise_add", {"X": ["z"], "Y": ["w"]},
                {"Out": ["o"]}, {})
    dot = program_to_dot(p)
    assert "digraph" in dot and "scale" in dot and "lightgrey" in dot


def test_passes_for_build_strategy_mapping():
    bs = fluid.BuildStrategy()
    # the one pass no field selects: attention chains a kernel covers
    assert [p.name for p in passes_for_build_strategy(bs)] \
        == ["fuse_attention"]
    bs.memory_optimize = True
    names = [p.name for p in passes_for_build_strategy(bs)]
    assert names == ["constant_fold", "fuse_attention", "prune_identity",
                     "dce"]
    bs.fuse_elewise_add_act_ops = True
    bs.fuse_bn_act_ops = True
    bs.fuse_all_reduce_ops = True
    names = [p.name for p in passes_for_build_strategy(bs)]
    assert names == ["constant_fold", "fuse_elewise_add_act",
                     "fuse_bn_act", "fuse_attention", "prune_identity",
                     "dce", "coalesce_allreduce"]
    # every pipeline holds it, under any sharding: what it rewrites there
    # is the mesh's answer (tests/test_kernel_tier.py)
    for sharding in ("dp", "fsdp", "tp"):
        bs.sharding = sharding
        assert [p.name for p in passes_for_build_strategy(bs)].count(
            "fuse_attention") == 1


def test_compiled_program_applies_passes_once():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        h = fluid.layers.fc(x, 8, act="relu")
        out = fluid.layers.reduce_sum(h)
    bs = fluid.BuildStrategy()
    bs.fuse_elewise_add_act_ops = True
    cp = fluid.CompiledProgram(main, build_strategy=bs)
    exe = fluid.Executor()
    exe.run(startup)
    feed = {"x": np.ones((2, 4), "float32")}
    exe.run(cp, feed=feed, fetch_list=[out])
    v = main._version
    exe.run(cp, feed=feed, fetch_list=[out])    # second run: no re-apply
    assert main._version == v


def test_dce_later_fetch_of_pruned_var_names_the_cause():
    """Fetching a var DCE pruned (because the first run didn't ask for
    it) must raise an actionable error, not a bare KeyError."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        h = fluid.layers.fc(x, 8, act="relu")
        loss = fluid.layers.reduce_sum(h)
        metric = fluid.layers.scale(fluid.layers.reduce_mean(h), scale=2.0)
    bs = fluid.BuildStrategy()
    bs.enable_dce = True
    cp = fluid.CompiledProgram(main, build_strategy=bs)
    exe = fluid.Executor()
    exe.run(startup)
    feed = {"x": np.ones((2, 4), "float32")}
    exe.run(cp, feed=feed, fetch_list=[loss])      # seeds DCE with loss
    with pytest.raises(ValueError, match="dead-code elimination"):
        exe.run(cp, feed=feed, fetch_list=[metric])


# ---------------------------------------------------------------------------
# memory_optimize legacy shim
# ---------------------------------------------------------------------------

def test_memory_optimize_shim_routes_through_pass_manager():
    import warnings
    p, _ = _two_op_program()
    c0 = trace.metrics().counter(
        "pass.memory_optimize_legacy.programs_seen").value
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        fluid.memory_optimize(p)
        fluid.release_memory(p)
    assert sum(1 for x in w
               if issubclass(x.category, DeprecationWarning)) == 2
    assert trace.metrics().counter(
        "pass.memory_optimize_legacy.programs_seen").value == c0 + 2
    assert len(p.global_block().ops) == 2   # no-op: program untouched
