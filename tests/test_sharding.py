"""Unified SPMD sharding plane (parallel/sharding.py, docs/sharding.md):
rule engine, plan resolution, shard_collectives rewrite, the executor's
whole-step sharded compile, per-shard checkpoint IO, and the ring->axis
stamp on Fleet collectives.  Multi-device behavior (8 emulated CPU
devices) runs in subprocess children (tests/sharding_worker.py) since the
device count is fixed at jax init."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace
from paddle_tpu.fluid.core import Scope, scope_guard, global_scope
from paddle_tpu.fluid.framework import reset_unique_name
from paddle_tpu.parallel import sharding as shd
from paddle_tpu.parallel import mesh as mesh_registry
from paddle_tpu.parallel import api as papi

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_names_and_mesh():
    reset_unique_name()
    prev = mesh_registry.current_mesh()
    yield
    mesh_registry.set_current_mesh(prev)


def one_dev_mesh(axis="dp"):
    return mesh_registry.build_mesh({axis: 1}, devices=jax.devices()[:1])


# ---------------------------------------------------------------------------
# demo programs: the BERT- and CTR-shaped static programs the rule-
# coverage satellite names (bench.py's fluid-program legs, sans BoxPS)
# ---------------------------------------------------------------------------

def build_bert_demo(vocab=64, hidden=16, seq=8):
    m, s = fluid.Program(), fluid.Program()
    with fluid.program_guard(m, s):
        ids = fluid.data("ids", [-1, seq], dtype="int64")
        labels = fluid.data("labels", [-1, 1], dtype="int64")
        emb = fluid.layers.embedding(ids, size=[vocab, hidden])
        h = fluid.layers.layer_norm(emb)
        h = fluid.layers.fc(h, hidden * 4, act="relu", num_flatten_dims=2)
        h = fluid.layers.fc(h, hidden, num_flatten_dims=2)
        pooled = fluid.layers.reduce_mean(h, dim=1)
        logits = fluid.layers.fc(pooled, 2)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, labels))
        opt = fluid.optimizer.AdamOptimizer(1e-3)
        _, pg = opt.minimize(loss)
    return m, s, loss, pg


def build_ctr_demo(slots=4, dim=8):
    m, s = fluid.Program(), fluid.Program()
    with fluid.program_guard(m, s):
        ids = fluid.data("ids", [-1, slots], dtype="int64")
        dense = fluid.data("dense", [-1, 13])
        label = fluid.data("label", [-1, 1])
        emb = fluid.layers.embedding(ids, size=[128, dim])
        flat = fluid.layers.reshape(emb, [-1, slots * dim])
        deep = fluid.layers.concat([flat, dense], axis=1)
        h = fluid.layers.fc(deep, 32, act="relu")
        wide = fluid.layers.fc(dense, 1)
        logit = fluid.layers.fc(h, 1) + wide
        loss = fluid.layers.mean(
            fluid.layers.sigmoid_cross_entropy_with_logits(logit, label))
        opt = fluid.optimizer.SGDOptimizer(0.1)
        _, pg = opt.minimize(loss)
    return m, s, loss, pg


def build_mlp_demo():
    m, s = fluid.Program(), fluid.Program()
    with fluid.program_guard(m, s):
        x = fluid.data("x", [-1, 16])
        y = fluid.data("y", [-1, 1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu")
        logits = fluid.layers.fc(h, 10)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        opt = fluid.optimizer.AdamOptimizer(1e-2)
        _, pg = opt.minimize(loss)
    return m, s, loss, pg


def mlp_feed(n=16):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(n, 16).astype("float32"),
            "y": rng.randint(0, 10, (n, 1)).astype("int64")}


# ---------------------------------------------------------------------------
# rule engine
# ---------------------------------------------------------------------------

def test_match_partition_rules_first_match_and_scalars():
    rules = [(r"w$", P(None, "tp")), (r".*", P())]
    specs = shd.match_partition_rules(
        rules, {"enc/w": (4, 8), "enc/b": (8,), "step": ()})
    assert specs["enc/w"] == P(None, "tp")
    assert specs["enc/b"] == P()
    assert specs["step"] == P()          # scalars never partition

    # first match wins, search (not fullmatch) semantics
    specs = shd.match_partition_rules(
        [(r"w", P("tp")), (r"w_0", P())], {"fc.w_0": (8, 8)})
    assert specs["fc.w_0"] == P("tp")


def test_match_partition_rules_strict_mode_raises():
    with pytest.raises(ValueError, match="Partition rule not found"):
        shd.match_partition_rules([], {"orphan": (4, 4)},
                                  on_unmatched="raise")


def test_unmatched_falls_back_replicated_with_counter(capfd):
    c0 = trace.metrics().counter("sharding.unmatched_params").value
    specs = shd.match_partition_rules([(r"^never$", P("dp"))],
                                      {"lonely_var": (8, 4)})
    assert specs["lonely_var"] == P()
    assert trace.metrics().counter(
        "sharding.unmatched_params").value == c0 + 1
    # the warning is one-shot per process; a second miss only counts
    shd.match_partition_rules([], {"other_var": (8, 4)})
    assert trace.metrics().counter(
        "sharding.unmatched_params").value == c0 + 2
    err = capfd.readouterr().err
    assert err.count("matched no partition rule") <= 1


def test_fsdp_spec_resolution_picks_first_divisible_dim():
    assert shd._resolve_fsdp((6, 8), "dp", 4) == P(None, "dp")
    assert shd._resolve_fsdp((8, 6), "dp", 4) == P("dp")
    assert shd._resolve_fsdp((3, 5), "dp", 4) == P()   # undividable


def test_tuple_and_none_specs_normalise():
    specs = shd.match_partition_rules(
        [(r"a", (None, "tp")), (r"b", None)], {"a": (4, 4), "b": (4, 4)})
    assert specs["a"] == P(None, "tp")
    assert specs["b"] == P()


# ---------------------------------------------------------------------------
# rule coverage over the demo programs (the satellite's contract: every
# param/accumulator resolves to exactly one spec; unmatched only ever
# means replicated-with-counter)
# ---------------------------------------------------------------------------

def _coverage(plan, program):
    blk = program.global_block()
    out = {}
    for n, v in blk.vars.items():
        if v.persistable:
            shape = tuple(d for d in (v.shape or ()) if d != -1)
            out[n] = plan.spec_for(n, shape)
    return out


@pytest.mark.parametrize("mode", ["dp", "fsdp", "tp"])
def test_bert_demo_every_param_and_accumulator_has_one_spec(mode):
    m, _, _, _ = build_bert_demo()
    mesh = one_dev_mesh("tp" if mode == "tp" else "dp")
    c0 = trace.metrics().counter("sharding.unmatched_params").value
    plan = shd.build_plan(program=m, mode=mode, mesh=mesh)
    specs = _coverage(plan, m)
    assert len(specs) >= 12           # params + Adam moments + pows + lr
    assert all(isinstance(s, P) for s in specs.values())
    if mode == "dp":
        assert all(s == P() for s in specs.values())
        assert trace.metrics().counter(
            "sharding.unmatched_params").value == c0
    if mode == "tp":
        # the embedding table and at least one matmul weight shard
        emb = [n for n in specs if "emb" in n and not n.startswith("Adam")]
        # trailing None dims are normalised away by the mesh clip
        assert emb and specs[emb[0]] in (P("tp"), P("tp", None))
        assert any("tp" in str(s) for n, s in specs.items()
                   if n.startswith("fc."))


@pytest.mark.parametrize("mode", ["dp", "fsdp"])
def test_ctr_demo_every_param_and_accumulator_has_one_spec(mode):
    m, _, _, _ = build_ctr_demo()
    c0 = trace.metrics().counter("sharding.unmatched_params").value
    plan = shd.build_plan(program=m, mode=mode, mesh=one_dev_mesh())
    specs = _coverage(plan, m)
    assert len(specs) >= 8            # emb + 3 fc pairs + lr
    assert all(isinstance(s, P) for s in specs.values())
    # dp and fsdp rule sets cover everything — no replicated fallback
    assert trace.metrics().counter(
        "sharding.unmatched_params").value == c0


def test_accumulator_inherits_param_spec():
    m, _, _, _ = build_mlp_demo()
    mesh = one_dev_mesh("tp")
    plan = shd.build_plan(program=m, mode="tp", mesh=mesh)
    w_spec = plan.spec_for("fc.w_0", (16, 32))
    assert w_spec == P(None, "tp")
    # same-shaped Adam moments ride the param's placement
    assert plan.spec_for("AdamOptimizer_moment1_fc.w_0", (16, 32)) == w_spec
    assert plan.spec_for("AdamOptimizer_moment2_fc.w_0", (16, 32)) == w_spec
    # the (1,)-shaped beta-pow accumulators replicate (scalar guard)
    assert plan.spec_for("AdamOptimizer_beta1_pow_fc.w_0", (1,)) == P()
    assert plan.base_param_of("AdamOptimizer_moment1_fc.w_0") == "fc.w_0"
    assert plan.base_param_of("fc.w_0@GRAD") == "fc.w_0"


def test_plan_clips_specs_to_mesh_axes():
    # a tp rule set on a dp-only mesh degrades to replicated, and a dim
    # that does not divide the axis degrades too — never an XLA error
    plan = shd.ShardingPlan(one_dev_mesh("dp"),
                            [(r"w", P(None, "tp")), (r"odd", P("dp"))],
                            param_names=["w", "odd"])
    assert plan.spec_for("w", (4, 4)) == P()
    mesh_registry.set_current_mesh(None)


def test_plan_describe_is_jsonable():
    m, _, _, _ = build_mlp_demo()
    plan = shd.build_plan(program=m, mode="dp", mesh=one_dev_mesh())
    d = json.loads(json.dumps(plan.describe()))
    assert d["mode"] == "dp" and d["mesh_shape"] == {"dp": 1}


def test_hybrid_schema_routes_through_rule_engine():
    from paddle_tpu.parallel.hybrid import TransformerConfig, param_schema
    schema = param_schema(TransformerConfig())
    assert schema["embed"][1] == P("tp", None)
    assert schema["w1"][1] == P("pp", None, "tp")
    specs = shd.match_partition_rules(
        shd.HYBRID_RULES, {n: s[0] for n, s in schema.items()},
        on_unmatched="raise")
    assert all(specs[n] == schema[n][1] for n in schema)


def test_moe_rules_through_engine():
    from paddle_tpu.parallel.moe import moe_partition_rules
    specs = shd.match_partition_rules(
        moe_partition_rules(), {"layer_0.router.w": (16, 8),
                                "layer_0.experts.gate": (8, 16, 32),
                                "layer_0.experts.up": (8, 16, 32),
                                "layer_0.experts.down": (8, 32, 16)},
        on_unmatched="raise")
    assert specs["layer_0.router.w"] == P()
    assert specs["layer_0.experts.gate"] == P("ep", None, None)
    assert specs["layer_0.experts.down"] == P("ep", None, None)


# ---------------------------------------------------------------------------
# satellite: ring -> mesh-axis stamp on Fleet collectives
# ---------------------------------------------------------------------------

def test_insert_allreduce_ops_stamps_mesh_axis():
    from paddle_tpu.distributed.fleet.meta_optimizers.common import \
        insert_allreduce_ops
    m, _, _, pg = build_mlp_demo()
    insert_allreduce_ops(m.global_block(), pg)
    ars = [op for op in m.global_block().ops
           if op.type == "c_allreduce_avg"]
    assert ars and all(op.attrs["mesh_axis"] == "dp" for op in ars)
    assert all(op.attrs["ring_id"] == 0 for op in ars)


def test_custom_ring_maps_to_registered_axis():
    from paddle_tpu.distributed.fleet.meta_optimizers.common import \
        insert_allreduce_ops
    mesh_registry.register_ring(7, "ep")
    try:
        assert mesh_registry.axis_for_ring(7) == "ep"
        m, _, _, pg = build_mlp_demo()
        insert_allreduce_ops(m.global_block(), pg, ring_id=7)
        ars = [op for op in m.global_block().ops
               if op.type == "c_allreduce_avg"]
        assert ars and all(op.attrs["mesh_axis"] == "ep" for op in ars)
    finally:
        mesh_registry._ring_axes.pop(7, None)


def test_coalesce_preserves_mesh_axis_and_shard_collectives_maps_it():
    from paddle_tpu.distributed.fleet.meta_optimizers.common import \
        insert_allreduce_ops
    from paddle_tpu.fluid.passes import PassPipeline, create_pass
    m, _, loss, pg = build_mlp_demo()
    insert_allreduce_ops(m.global_block(), pg)
    pipe = PassPipeline([create_pass("coalesce_allreduce", bucket_size=8)])
    pipe.apply(m, targets=[loss.name])
    co = [op for op in m.global_block().ops
          if op.type == "c_allreduce_coalesced"]
    assert co and co[0].attrs["mesh_axis"] == "dp"
    stats = PassPipeline([create_pass("shard_collectives")]).apply(
        m, targets=[loss.name])
    assert stats["shard_collectives"]["collectives_implied"] == len(pg)
    sc = [op for op in m.global_block().ops
          if op.type == "shard_constraint"]
    assert sc and sc[0].attrs["mesh_axis"] == "dp"
    assert sc[0].attrs["origin"] == "c_allreduce_coalesced"
    assert not any(op.type.startswith("c_allreduce")
                   for op in m.global_block().ops)


# ---------------------------------------------------------------------------
# shard_collectives rewrite + executor sharded path (1-device mesh: the
# code path is identical, the communication degenerate)
# ---------------------------------------------------------------------------

def _run_losses(exe, prog, loss, feed, steps=4):
    return [float(np.asarray(exe.run(prog, feed=feed,
                                     fetch_list=[loss])[0]).ravel()[0])
            for _ in range(steps)]


@pytest.mark.parametrize("n_dev", [1, 8])
def test_sharded_dp_executor_parity_with_plain(n_dev):
    # conftest forces 8 virtual CPU devices: n_dev=8 is REAL in-process
    # multi-chip DP.  A 1-device mesh is bit-identical to the plain
    # path; 8 shards reorder the batch reduction (allclose).
    feed = mlp_feed()
    m, s, loss, _ = build_mlp_demo()
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(s)
        base = _run_losses(exe, m, loss, feed)

    reset_unique_name()
    m2, s2, loss2, pg2 = build_mlp_demo()
    from paddle_tpu.distributed.fleet.meta_optimizers.common import \
        insert_allreduce_ops
    insert_allreduce_ops(m2.global_block(), pg2)
    bs = fluid.BuildStrategy()
    bs.sharding = "dp"
    bs.sharding_mesh = {"dp": n_dev}
    cp = fluid.CompiledProgram(m2, build_strategy=bs)
    d0 = trace.metrics().counter("sharding.collectives_dispatched").value
    exe2 = fluid.Executor()
    with scope_guard(Scope()):
        exe2.run(s2)
        got = _run_losses(exe2, cp, loss2, feed)
    if n_dev == 1:
        assert got == base                   # 1-dev mesh: bit-identical
    else:
        np.testing.assert_allclose(got, base, rtol=1e-4)
    assert cp._sharding_plan is not None
    assert cp._sharding_plan.n_devices == n_dev
    # the rewritten collectives never dispatch a per-op psum
    assert trace.metrics().counter(
        "sharding.collectives_dispatched").value == d0
    assert m2._hints["sharding"]["mode"] == "dp"


def test_rewritten_program_still_runs_unsharded():
    # fallback: the shard_constraint op is identity without a live mesh
    feed = mlp_feed()
    m, s, loss, pg = build_mlp_demo()
    from paddle_tpu.distributed.fleet.meta_optimizers.common import \
        insert_allreduce_ops
    from paddle_tpu.fluid.passes import PassPipeline, create_pass
    insert_allreduce_ops(m.global_block(), pg)
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(s)
        before = _run_losses(exe, m, loss, feed, steps=2)
    PassPipeline([create_pass("shard_collectives")]).apply(
        m, targets=[loss.name])
    reset_unique_name()
    m2, s2, loss2, pg2 = build_mlp_demo()
    exe2 = fluid.Executor()
    with scope_guard(Scope()):
        exe2.run(s2)
        plain = _run_losses(exe2, m2, loss2, feed, steps=2)
    exe3 = fluid.Executor()       # fresh: a reused executor's advanced
    with scope_guard(Scope()):    # PRNG step re-randomises startup init
        exe3.run(s)
        after = _run_losses(exe3, m, loss, feed, steps=2)
    assert before == plain == after


@pytest.mark.parametrize("mode", ["tp", "fsdp"])
def test_sharded_modes_parity_one_device(mode):
    feed = mlp_feed()
    m, s, loss, _ = build_mlp_demo()
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(s)
        base = _run_losses(exe, m, loss, feed)
    reset_unique_name()
    m2, s2, loss2, _ = build_mlp_demo()
    bs = fluid.BuildStrategy()
    bs.sharding = mode
    bs.sharding_mesh = {"tp" if mode == "tp" else "dp": 1}
    cp = fluid.CompiledProgram(m2, build_strategy=bs)
    exe2 = fluid.Executor()
    with scope_guard(Scope()):
        exe2.run(s2)
        got = _run_losses(exe2, cp, loss2, feed)
    assert np.allclose(got, base, rtol=1e-6, atol=0)


def test_custom_rules_knob():
    feed = mlp_feed()
    m, s, loss, _ = build_mlp_demo()
    bs = fluid.BuildStrategy()
    bs.sharding = [(r"\.w_", P(None, "dp")), (r".*", P())]
    bs.sharding_mesh = {"dp": 1}
    cp = fluid.CompiledProgram(m, build_strategy=bs)
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(s)
        got = _run_losses(exe, cp, loss, feed, steps=2)
    assert np.all(np.isfinite(got))
    assert cp._sharding_plan.spec_for("fc.w_0", (16, 32)) == P(None, "dp")
    assert cp._sharding_plan.mode == "custom"


def test_run_scan_rejects_sharded_programs():
    from paddle_tpu.fluid.async_pipeline import ScanUnsupportedError
    m, s, loss, _ = build_mlp_demo()
    bs = fluid.BuildStrategy()
    bs.sharding = "dp"
    bs.sharding_mesh = {"dp": 1}
    cp = fluid.CompiledProgram(m, build_strategy=bs)
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(s)
        with pytest.raises(ScanUnsupportedError):
            exe.run_scan(cp, feed_list=[mlp_feed(), mlp_feed()],
                         fetch_list=[loss])


# ---------------------------------------------------------------------------
# satellite: one shared mesh
# ---------------------------------------------------------------------------

def test_both_planes_share_one_mesh_object():
    mesh = one_dev_mesh("dp")
    # explicit plane resolves the SAME object...
    assert papi.resolved_mesh() is mesh
    # ...and a plan built with no explicit mesh adopts it too
    m, _, _, _ = build_mlp_demo()
    plan = shd.build_plan(program=m, mode="dp")
    assert plan.mesh is mesh
    # an explicit mesh becomes the shared one
    mesh2 = mesh_registry.build_mesh({"tp": 1}, devices=jax.devices()[:1])
    assert papi.resolved_mesh(mesh2) is mesh2
    assert mesh_registry.current_mesh() is mesh2


def test_compat_shard_map_executes():
    mesh = one_dev_mesh("dp")
    f = papi.compat_shard_map(lambda x: x * 2, mesh,
                              in_specs=P(), out_specs=P())
    out = jax.jit(f)(np.ones((4,), np.float32))
    assert np.array_equal(np.asarray(out), np.full((4,), 2.0, np.float32))


# ---------------------------------------------------------------------------
# make_shard_and_gather_fns + checkpoint piece algebra
# ---------------------------------------------------------------------------

def test_make_shard_and_gather_fns_roundtrip():
    m, _, _, _ = build_mlp_demo()
    plan = shd.build_plan(program=m, mode="dp", mesh=one_dev_mesh())
    arrs = {"fc.w_0": np.arange(12, dtype=np.float32).reshape(3, 4)}
    shard_fns, gather_fns = shd.make_shard_and_gather_fns(plan, arrs)
    dev = shard_fns["fc.w_0"](arrs["fc.w_0"])
    assert hasattr(dev, "sharding")
    back = gather_fns["fc.w_0"](dev)
    assert np.array_equal(back, arrs["fc.w_0"])


def test_assemble_slice_from_pieces():
    from paddle_tpu.fluid import checkpoint as ckpt
    full = np.arange(32, dtype=np.float32).reshape(8, 4)
    pieces = [(((0, 4), (0, 4)), (lambda: full[0:4])),
              (((4, 8), (0, 4)), (lambda: full[4:8]))]
    # whole array
    got = ckpt._assemble_slice((slice(0, 8), slice(0, 4)), (8, 4),
                               np.float32, pieces)
    assert np.array_equal(got, full)
    # a slice straddling both pieces (the resharded-restore case)
    got = ckpt._assemble_slice((slice(2, 6), slice(0, 4)), (8, 4),
                               np.float32, pieces)
    assert np.array_equal(got, full[2:6])
    # uncovered region raises, never returns junk
    with pytest.raises(ckpt.CorruptCheckpointError):
        ckpt._assemble_slice(
            (slice(0, 8), slice(0, 4)), (8, 4), np.float32, pieces[:1])


def test_norm_index_pads_missing_dims():
    from paddle_tpu.fluid import checkpoint as ckpt
    assert ckpt._norm_index((slice(2, 4),), (8, 4)) == ((2, 4), (0, 4))
    assert ckpt._norm_index((slice(None), slice(None)), (8, 4)) \
        == ((0, 8), (0, 4))


def test_donation_guard_persists_sharded_snapshots_per_shard(tmp_path):
    # the TPU-mode hazard: a donating dispatch overtakes the background
    # writer and the alias guard persists every snapshot handle.  For
    # mesh-sharded state that persist must be PER SHARD, never a full
    # gather — and the checkpoint written from the guard-persisted
    # pieces must still restore bit-exactly.
    from paddle_tpu.fluid import checkpoint as ckpt
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    mesh = mesh_registry.build_mesh({"dp": 8})
    full = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
    arr = jax.device_put(full, NamedSharding(mesh, P("dp")))
    h = ckpt._snapshot_handle(arr, "w")
    assert type(h).__name__ == "_ShardSnapshotHandle"
    orig = ckpt._to_host
    ckpt._to_host = lambda hh: (_ for _ in ()).throw(
        AssertionError("full-host gather on sharded snapshot"))
    try:
        h.persist()                      # the alias guard's call
        assert h.sharded_pieces is not None
        assert len(h.sharded_pieces.pieces) == 8
        assert h.persist() is None       # idempotent, still no gather
        # the writer consumes the guard-persisted pieces
        mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
        job = ckpt._SaveJob(1, {"w": h},
                            dict(format_version=ckpt.FORMAT_VERSION,
                                 step=1, reason="test", cursor={},
                                 extra={}, numpy_rng=None,
                                 random_seed=None, executor_step=None,
                                 optimizer_state=None, wall_time=0.0),
                            sync=True)
        mgr._run_job(job)
        assert job.error is None, job.error
    finally:
        ckpt._to_host = orig
    with scope_guard(Scope()):
        mgr2 = ckpt.CheckpointManager(str(tmp_path))
        mgr2.restore(strict=False)
        assert np.array_equal(
            np.asarray(global_scope().find_var("w")), full)


def test_tp_rules_are_total_over_params():
    # replicated row biases / tail params get an explicit P() rule, so a
    # tp plan never fires the unmatched fallback for a covered model
    m, _, _, _ = build_mlp_demo()
    c0 = trace.metrics().counter("sharding.unmatched_params").value
    plan = shd.build_plan(program=m, mode="tp", mesh=one_dev_mesh("tp"))
    _coverage(plan, m)
    assert trace.metrics().counter(
        "sharding.unmatched_params").value == c0
    # ...while accumulators still INHERIT (the explicit rules cover
    # params only, never short-circuiting suffix derivation)
    assert plan.spec_for("AdamOptimizer_moment1_fc.w_0", (16, 32)) \
        == plan.spec_for("fc.w_0", (16, 32)) != P()


def test_engine_rejects_mesh_for_aot_artifacts():
    from paddle_tpu import serving

    class FakeAot:
        def call_lazy(self, feed):       # quacks like AotPredictor
            return []

    with pytest.raises(ValueError, match="cannot be re-sharded"):
        serving.ServingEngine(FakeAot(), mesh=one_dev_mesh("tp"))


def test_checkpoint_plan_roundtrip_one_device(tmp_path):
    from paddle_tpu.fluid import checkpoint as ckpt
    feed = mlp_feed()
    m, s, loss, _ = build_mlp_demo()
    bs = fluid.BuildStrategy()
    bs.sharding = "dp"
    bs.sharding_mesh = {"dp": 1}
    cp = fluid.CompiledProgram(m, build_strategy=bs)
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(s)
        _run_losses(exe, cp, loss, feed, steps=2)
        ref = {n: np.asarray(global_scope().find_var(n))
               for n in ("fc.w_0", "AdamOptimizer_moment1_fc.w_0")}
        mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(program=cp, executor=exe, step=2, sync=True)
        mgr.close()
    with scope_guard(Scope()):
        mgr2 = ckpt.CheckpointManager(str(tmp_path))
        st = mgr2.restore(program=cp)     # plan auto-detected from cp
        assert st is not None and st.step == 2
        for n, v in ref.items():
            assert np.array_equal(
                np.asarray(global_scope().find_var(n)), v), n


# ---------------------------------------------------------------------------
# serving + device stats customers
# ---------------------------------------------------------------------------

def test_freeze_with_mesh_stamps_plan_and_serves():
    from paddle_tpu import serving
    m, s = fluid.Program(), fluid.Program()
    with fluid.program_guard(m, s):
        x = fluid.data("x", [-1, 16])
        h = fluid.layers.fc(x, 32, act="relu")
        logits = fluid.layers.fc(h, 10)
    exe = fluid.Executor()
    exe.run(s)
    xv = np.random.RandomState(0).randn(4, 16).astype("float32")
    plain = serving.freeze_program(m, ["x"], [logits])
    ref, = exe.run(plain, feed={"x": xv}, fetch_list=[logits.name])
    mesh = mesh_registry.build_mesh({"tp": 1}, devices=jax.devices()[:1])
    frozen = serving.freeze_program(m, ["x"], [logits], mesh=mesh)
    assert frozen._sharding_plan is not None
    assert frozen._hints["sharding"]["mode"] == "tp"
    got, = exe.run(frozen, feed={"x": xv}, fetch_list=[logits.name])
    assert np.allclose(np.asarray(got), np.asarray(ref),
                       rtol=1e-6, atol=0)


def test_engine_accepts_mesh():
    from paddle_tpu import serving
    m, s = fluid.Program(), fluid.Program()
    with fluid.program_guard(m, s):
        x = fluid.data("x", [-1, 8])
        out = fluid.layers.fc(x, 4)
    exe = fluid.Executor()
    exe.run(s)
    frozen = serving.freeze_program(m, ["x"], [out])
    mesh = mesh_registry.build_mesh({"tp": 1}, devices=jax.devices()[:1])
    with serving.ServingEngine(frozen, mesh=mesh) as eng:
        fut = eng.submit(
            {"x": np.ones((2, 8), np.float32)})
        res = fut.result(timeout=30)
    assert res[out.name].shape == (2, 4)
    assert frozen._sharding_plan is not None


def test_device_stats_capture_records_mesh_devices():
    from paddle_tpu.fluid import device_stats
    jitted = jax.jit(lambda a: a @ a)
    info = device_stats.capture(
        jitted, [np.ones((8, 8), np.float32)], label="shardtest",
        n_devices=4)
    assert info is not None
    assert info["mesh_devices"] == 4
    assert info["per_device_peak_bytes"] == info["peak_bytes"]
    device_stats.unpublish("shardtest")


# ---------------------------------------------------------------------------
# multi-device truth (8 emulated CPU devices, subprocess)
# ---------------------------------------------------------------------------

def _run_worker(mode, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"))
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tests",
                                      "sharding_worker.py"), mode],
        capture_output=True, text=True, timeout=timeout, cwd=_ROOT,
        env=env)
    assert r.returncode == 0, f"{mode}: {r.stdout}\n{r.stderr}"
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("{")][-1]
    return json.loads(line)


def test_eight_device_dp_parity_and_zero_dispatched_collectives():
    info = _run_worker("dp_parity")
    assert info["ok"] and info["devices"] == 8
    assert info["collectives_dispatched"] == 0
    assert info["collectives_implied"] > 0
    assert info["mesh_shape"] == {"dp": 8}
    np.testing.assert_allclose(info["loss_sharded"], info["loss_base"],
                               rtol=1e-4)


def test_eight_device_resharded_checkpoint_roundtrip():
    info = _run_worker("reshard")
    assert info["ok"] and info["saved_devices"] == 8
    assert info["restored_devices"] == 4


# ---------------------------------------------------------------------------
# Pallas kernels inside a partitioned program: directly, per shard, or not
# (ops/registry.py LoweringContext.kernel_site)
# ---------------------------------------------------------------------------

def _mesh(**axes):
    n = int(np.prod(list(axes.values())))
    return mesh_registry.build_mesh(axes, devices=jax.devices()[:n])


@pytest.mark.parametrize("axes, want", [
    ({"dp": 4}, "dp"), ({"fsdp": 4}, "fsdp"), ({"data": 2}, "data"),
    ({"dp": 4, "tp": 1}, "dp"), ({"dp": 1}, "dp"),
    ({"tp": 4}, None), ({"dp": 2, "tp": 2}, None), ({"pp": 2, "dp": 2}, None),
])
def test_batch_shard_axis(axes, want):
    assert shd.batch_shard_axis(_mesh(**axes)) == want
    assert shd.batch_shard_axis(None) is None


@pytest.mark.parametrize("why, backend, mode, axes, batch, want", [
    ("unpartitioned: the kernel is called directly",
     "tpu", None, None, 6, 1),
    ("a one-device plan is not partitioned", "tpu", "dp", {"dp": 1}, 6, 1),
    ("dp: once per shard", "tpu", "dp", {"dp": 4}, 8, 4),
    ("fsdp shards activations the same way", "tpu", "fsdp", {"dp": 4}, 8, 4),
    ("a further axis of size 1 changes nothing",
     "tpu", "dp", {"dp": 2, "tp": 1}, 8, 2),
    ("tp: no kernel here", "tpu", "tp", {"tp": 4}, 8, None),
    ("dp x tp: no kernel here", "tpu", "dp", {"dp": 2, "tp": 2}, 8, None),
    ("a batch the axis does not divide", "tpu", "dp", {"dp": 4}, 6, None),
    ("off the chip", "cpu", "dp", {"dp": 4}, 8, None),
    ("off the chip, unpartitioned", "cpu", None, None, 8, None),
])
def test_kernel_site_is_read_from_the_plan(monkeypatch, why, backend, mode,
                                           axes, batch, want):
    from paddle_tpu.ops.registry import LoweringContext
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    ctx = LoweringContext()
    if mode is not None:
        m, _, _, _ = build_mlp_demo()
        plan = shd.build_plan(m, mode=mode, mesh=_mesh(**axes))
        # as fluid/executor.py sets them
        ctx.mesh = plan.mesh
        ctx.partitioned = plan.mesh.devices.size > 1
    x = jax.ShapeDtypeStruct((batch, 128), np.float32)
    site = ctx.kernel_site(x)
    assert (site and site.shards) == want, why
    # "a kernel may be called directly" is the narrower question
    assert ctx.pallas_ok() == (want == 1)
    if want:
        assert site.local(x).shape == (batch // want, 128)


def test_kernel_site_refuses_an_op_over_a_parameter(monkeypatch):
    """Rows of a parameter are not the batch: sharding them for a kernel
    would reshard a replicated array."""
    from paddle_tpu.ops.registry import LoweringContext
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ctx = LoweringContext()
    ctx.mesh, ctx.partitioned = _mesh(dp=4), True
    x = jax.ShapeDtypeStruct((8, 128), np.float32)
    assert ctx.kernel_site(x).shards == 4
    ctx.cur_op_batch_major = False
    assert ctx.kernel_site(x) is None
    ctx.partitioned = False              # directly: no rows are split
    assert ctx.kernel_site(x).shards == 1


def _bert_step(sharding, dropout=0.0):
    """One training step of a one-layer BERT at widths the fused attention
    kernel covers; returns the loss, every gradient, and the counters'
    movement."""
    from paddle_tpu.models.static_graphs import (bert_demo_feed,
                                                 build_bert_train_program)
    reset_unique_name()
    main, startup, loss = build_bert_train_program(
        vocab=64, hidden=128, heads=2, seq=128, layers=1, dropout=dropout)
    bs = fluid.BuildStrategy()
    if sharding:
        bs.sharding = sharding
        bs.sharding_mesh = {"dp": 4}
    prog = fluid.CompiledProgram(main, build_strategy=bs)
    feed = bert_demo_feed(np.random.RandomState(0), batch=8, seq=128,
                          vocab=64)
    grads = [p.name + "@GRAD" for p in main.all_parameters()]
    names = ("attention.lowering.fused_kernel", "attention.lowering.xla",
             "kernel.shard_map_calls")
    before = {n: trace.metrics().counter(n).value for n in names}
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        out = exe.run(prog, feed=feed, fetch_list=[loss] + grads)
    moved = {n: trace.metrics().counter(n).value - before[n] for n in names}
    types = [op.type for op in main.global_block().ops]
    return [np.asarray(o) for o in out], moved, types


@pytest.mark.parametrize("mode", ["dp", "fsdp"])
def test_dp_program_runs_the_attention_kernel_per_shard(monkeypatch, mode):
    """The same Program unpartitioned and partitioned on the batch over 4
    devices, the kernels in the TPU interpreter: the default pipeline
    fuses the attention chain in both, the partitioned step lowers it
    under shard_map, and loss and gradients agree."""
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        one, moved_one, types_one = _bert_step(None)
        dp, moved_dp, types_dp = _bert_step(mode)
    assert types_dp == types_one
    assert types_dp.count("fused_multihead_attention") == 1
    # lowered once: the grad op applies the vjp the forward op kept
    assert moved_one == {"attention.lowering.fused_kernel": 1,
                         "attention.lowering.xla": 0,
                         "kernel.shard_map_calls": 0}
    assert moved_dp == {"attention.lowering.fused_kernel": 1,
                        "attention.lowering.xla": 0,
                        "kernel.shard_map_calls": 1}
    np.testing.assert_allclose(dp[0], one[0], rtol=1e-5)
    for got, want in zip(dp[1:], one[1:]):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6)
