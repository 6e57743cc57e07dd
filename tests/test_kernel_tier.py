"""Pallas kernel tier as compiler passes (fluid/passes/kernel_tier.py):
fuse_attention / fuse_sparse_embedding / fuse_paged_attention
pattern-rewrites, their negative cases (patterns must NOT fire), where a
kernel runs (the pass and the lowering ask the same two functions), and
the kernel-tier satellites (additive-bias mask dispatch, interpret-mode
kernel numerics)."""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers as L
from paddle_tpu.fluid import trace
from paddle_tpu.fluid.core import Scope, scope_guard
from paddle_tpu.fluid.framework import reset_unique_name
from paddle_tpu.fluid.passes import (PassPipeline, create_pass)
from paddle_tpu.models.static_graphs import (
    build_bert_train_program, build_ctr_train_program, bert_demo_feed,
    ctr_demo_feed)


@pytest.fixture(autouse=True)
def _fresh_names():
    # a sharding plan installs its mesh as the process's: every test's
    # default mesh must be its own
    from paddle_tpu.parallel import mesh as mesh_registry
    prev = mesh_registry.current_mesh()
    mesh_registry.set_current_mesh(None)
    reset_unique_name()
    yield
    mesh_registry.set_current_mesh(prev)


def _counter(name):
    return trace.metrics().counter(name).value


def _train(main, startup, loss, feed, n=10, build=None):
    ex = fluid.Executor()
    with scope_guard(Scope()):
        ex.run(startup)
        prog = main
        if build is not None:
            prog = fluid.CompiledProgram(main, build_strategy=build)
        losses = [float(np.asarray(
            ex.run(prog, feed=feed, fetch_list=[loss])[0]).ravel()[0])
            for _ in range(n)]
        scope = fluid.global_scope()
        params = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.all_parameters()}
    return losses, params


def _tier_bs(**kw):
    bs = fluid.BuildStrategy()
    for k, v in kw.items():
        setattr(bs, k, v)
    return bs


def _op_types(program):
    return [op.type for op in program.global_block().ops]


# ---------------------------------------------------------------------------
# fuse_attention — positive
# ---------------------------------------------------------------------------

# the pass rewrites a chain where its kernel runs: a length and a head
# width the fused attention kernel covers (S = 128, two heads of 64)
_COVERED = dict(hidden=128, heads=2, seq=128)


class TestFuseAttention:
    @pytest.mark.parametrize("dropout,with_mask", [
        (0.0, True), (0.1, True), (0.0, False), (0.1, False)])
    def test_train_rewrite_bit_parity(self, dropout, with_mask):
        """Every attention block (forward + grad) rewrites, the training
        trajectory is bit-identical on the CPU fallback — the absorbed
        dropout regenerates the same mask from the same op seed."""
        rng = np.random.RandomState(0)
        feed = bert_demo_feed(rng, batch=4, seq=_COVERED["seq"],
                              with_mask=with_mask)
        kw = dict(_COVERED, layers=2, dropout=dropout, with_mask=with_mask)
        l_off, p_off = _train(*build_bert_train_program(**kw), feed, n=4)
        reset_unique_name()
        r0 = _counter("kernel_tier.fuse_attention.rewrites")
        m, s, loss = build_bert_train_program(**kw)
        l_on, p_on = _train(m, s, loss, feed, n=4, build=_tier_bs())
        assert _counter("kernel_tier.fuse_attention.rewrites") - r0 == 2
        types = _op_types(m)
        assert types.count("fused_multihead_attention") == 2
        assert "softmax" not in types
        assert l_on == l_off
        for name in p_off:
            assert np.array_equal(p_off[name], p_on[name]), name

    def test_fwd_only_rewrite(self):
        """Inference-shaped programs (no grads) fuse through the
        fwd-only rules."""
        m, s = fluid.Program(), fluid.Program()
        with fluid.program_guard(m, s):
            ids = fluid.data("ids", [-1, 128], dtype="int64")
            h = L.embedding(ids, size=[32, 128])
            from paddle_tpu.models.static_graphs import _naive_attention
            h = _naive_attention(h, 128, 2)
            out = L.reduce_mean(h, dim=1)
        rng = np.random.RandomState(1)
        feed = {"ids": rng.randint(0, 32, (4, 128)).astype("int64")}
        ex = fluid.Executor()
        with scope_guard(Scope()):
            ex.run(s)
            want, = ex.run(m, feed=feed, fetch_list=[out])
            pipe = PassPipeline([create_pass("fuse_attention")])
            stats = pipe.apply(m, targets=[out.name])
            assert stats["fuse_attention"]["ops_fused"] == 1
            got, = ex.run(m, feed=feed, fetch_list=[out])
        assert np.array_equal(np.asarray(want), np.asarray(got))

    def test_rewrite_is_idempotent(self):
        m, s, loss = build_bert_train_program(layers=1, **_COVERED)
        pipe = PassPipeline([create_pass("fuse_attention")])
        stats1 = pipe.apply(m, targets=[loss.name])
        assert stats1["fuse_attention"]["ops_fused"] == 1
        v = m._version
        stats2 = PassPipeline([create_pass("fuse_attention")]).apply(
            m, targets=[loss.name])
        assert stats2["fuse_attention"].get("ops_fused", 0) == 0
        assert m._version == v

    def test_fused_op_carries_scale_and_dropout_attrs(self):
        m, s, loss = build_bert_train_program(layers=1, dropout=0.25,
                                              hidden=128, heads=4, seq=128)
        PassPipeline([create_pass("fuse_attention")]).apply(
            m, targets=[loss.name])
        op = next(o for o in m.global_block().ops
                  if o.type == "fused_multihead_attention")
        assert op.attrs["scale"] == pytest.approx((128 // 4) ** -0.5)
        assert op.attrs["dropout_rate"] == pytest.approx(0.25)
        assert op.attrs["dropout_seed"] > 0
        assert "Mask" in op.inputs


# ---------------------------------------------------------------------------
# fuse_attention — the patterns must NOT fire
# ---------------------------------------------------------------------------

def _qkv_data(seq=128, heads=2, dh=64):
    # a shape the kernel covers: a chain declines for the reason tested
    q = fluid.data("q", [-1, heads, seq, dh])
    k = fluid.data("k", [-1, heads, seq, dh])
    v = fluid.data("v", [-1, heads, seq, dh])
    return q, k, v


class TestFuseAttentionNegative:
    def test_multi_consumer_score_tensor(self):
        """The score tensor feeds a second consumer -> fusing it away
        would break that consumer; the rewrite must decline."""
        m, s = fluid.Program(), fluid.Program()
        with fluid.program_guard(m, s):
            q, k, v = _qkv_data()
            sc = L.matmul(q, k, transpose_y=True)
            p = L.softmax(sc)
            out = L.matmul(p, v)
            leak = L.reduce_mean(sc)        # second consumer of the score
        stats = PassPipeline([create_pass("fuse_attention")]).apply(
            m, targets=[out.name, leak.name])
        assert stats["fuse_attention"].get("ops_fused", 0) == 0
        assert "fused_multihead_attention" not in _op_types(m)

    def test_non_attention_matmul_softmax_chain(self):
        """A 2-d matmul->softmax->matmul (an mlp with a softmax gate) is
        not attention — the 4-d gate must keep it on the op-by-op path."""
        m, s = fluid.Program(), fluid.Program()
        with fluid.program_guard(m, s):
            x = fluid.data("x", [-1, 16])
            a = fluid.data("a", [-1, 16])
            b = fluid.data("b", [-1, 16])
            sc = L.matmul(x, a, transpose_y=True)
            p = L.softmax(sc)
            out = L.matmul(p, b)
        stats = PassPipeline([create_pass("fuse_attention")]).apply(
            m, targets=[out.name])
        assert stats["fuse_attention"].get("ops_fused", 0) == 0

    def test_the_same_chain_rewrites_when_nothing_else_reads_it(self):
        """The control for the cases around it: at this shape the plain
        chain is rewritten, so each of them declines for its own reason."""
        m, s = fluid.Program(), fluid.Program()
        with fluid.program_guard(m, s):
            q, k, v = _qkv_data()
            out = L.matmul(L.softmax(L.matmul(q, k, transpose_y=True)), v)
        stats = PassPipeline([create_pass("fuse_attention")]).apply(
            m, targets=[out.name])
        assert stats["fuse_attention"]["ops_fused"] == 1

    def test_fetched_probability_tensor_declines(self):
        """Fetching the softmax output keeps it protected: no rewrite."""
        m, s = fluid.Program(), fluid.Program()
        with fluid.program_guard(m, s):
            q, k, v = _qkv_data()
            p = L.softmax(L.matmul(q, k, transpose_y=True))
            out = L.matmul(p, v)
        stats = PassPipeline([create_pass("fuse_attention")]).apply(
            m, targets=[out.name, p.name])
        assert stats["fuse_attention"].get("ops_fused", 0) == 0


# ---------------------------------------------------------------------------
# fuse_attention in the default pipeline, the lowering's dispatch, and the
# fused attention kernel (ops/pallas_kernels.fused_attention_tpu)
# ---------------------------------------------------------------------------

def _default_pipeline(main, loss, **fields):
    """What CompiledProgram runs with only ``fields`` set (its plan, then
    its passes, as ``Executor.run`` asks for them); returns the rewrites
    counted."""
    prog = fluid.CompiledProgram(main, build_strategy=_tier_bs(**fields))
    r0 = _counter("kernel_tier.fuse_attention.rewrites")
    prog._ensure_sharding_plan()
    prog._apply_ir_passes([loss.name])
    return _counter("kernel_tier.fuse_attention.rewrites") - r0


# a BERT layer at a length and head width the fused kernel covers
_KERNEL_BERT = dict(hidden=128, heads=2, seq=512, layers=1, dropout=0.1)
_CUSTOM_RULES = [(r".*", ())]


class TestFuseAttentionByDefault:
    @pytest.mark.parametrize("fields", [
        {}, {"amp": True},
        # partitioned on the batch alone: the kernel runs once per chip
        {"sharding": "dp"}, {"sharding": "fsdp", "amp": True}])
    def test_rewrites_without_any_speed_field(self, fields):
        m, _, loss = build_bert_train_program(**_KERNEL_BERT)
        assert _default_pipeline(m, loss, **fields) == 1
        types = _op_types(m)
        assert types.count("fused_multihead_attention") == 1
        assert "softmax" not in types

    @pytest.mark.parametrize("why, model, fields", [
        ("no kernel under tensor parallelism", _KERNEL_BERT,
         {"sharding": "tp", "sharding_mesh": {"tp": 2}}),
        ("no kernel on a mesh with a further axis", _KERNEL_BERT,
         {"sharding": "dp", "sharding_mesh": {"dp": 2, "tp": 2}}),
        ("no kernel at this length", dict(_KERNEL_BERT, seq=16), {}),
        ("no kernel for this head width",
         dict(_KERNEL_BERT, hidden=48, heads=2), {}),
    ])
    def test_leaves_the_program_op_for_op(self, why, model, fields):
        m, _, loss = build_bert_train_program(**model)
        before = _op_types(m)
        assert _default_pipeline(m, loss, **fields) == 0
        assert [t for t in _op_types(m) if t != "shard_constraint"] \
            == before

    @pytest.mark.parametrize("sharding, mesh, rewrites", [
        (None, None, 1), ("dp", None, 1), ("FSDP", None, 1),
        ("tp", {"tp": 2}, 0),
        # custom rules on the default one-axis mesh: the mesh shards the
        # batch alone, so the chain follows the dropout ops beside it
        (_CUSTOM_RULES, None, 1)])
    def test_which_pipelines_hold_the_pass(self, sharding, mesh, rewrites):
        """Every pipeline holds the pass, once and without options; what
        it does under a sharding is the mesh's answer."""
        from paddle_tpu.fluid.passes import passes_for_build_strategy
        fields = dict(sharding=sharding, sharding_mesh=mesh)
        found = [p for p in passes_for_build_strategy(_tier_bs(**fields))
                 if p.name == "fuse_attention"]
        assert len(found) == 1
        m, _, loss = build_bert_train_program(**_KERNEL_BERT)
        assert _default_pipeline(m, loss, **fields) == rewrites

    @pytest.mark.parametrize("axes, batch, want", [
        ({"dp": 4}, -1, 1),       # an undeclared batch is not judged
        ({"dp": 4}, 8, 1),        # 2 rows a chip
        ({"dp": 4}, 6, 0),        # the axis does not divide the batch
        ({"dp": 2, "tp": 2}, 8, 0),   # no kernel in this partitioning
    ])
    def test_the_pass_judges_the_rows_one_chip_holds(self, axes, batch,
                                                     want):
        from types import SimpleNamespace as NS
        from jax.sharding import Mesh
        from paddle_tpu.fluid.passes.kernel_tier import FuseAttentionPass
        shapes = {"q": (batch, 12, 128, 64), "k": (batch, 12, 128, 64),
                  "v": (batch, 12, 128, 64), "mask": (batch, 1, 1, 128)}
        block = NS(_find_var_recursive=lambda n: NS(shape=shapes[n],
                                                    dtype="float32"))
        m = NS(block=block, var=lambda n: n, binding=shapes)
        n = int(np.prod(list(axes.values())))
        plan = NS(mesh=Mesh(np.array(jax.devices()[:n]).reshape(
            tuple(axes.values())), tuple(axes)))
        assert FuseAttentionPass._kernel_runs(m, None, plan) is bool(want)
        # without a plan the declared batch is one chip's
        assert FuseAttentionPass._kernel_runs(m, None) is True


def _attention_chain(batch, seq, heads=2, dh=64):
    """matmul -> scale -> softmax -> matmul over declared [B, H, S, D]."""
    m, s = fluid.Program(), fluid.Program()
    with fluid.program_guard(m, s):
        q, k, v = (fluid.data(n, [batch, heads, seq, dh]) for n in "qkv")
        sc = L.scale(L.matmul(q, k, transpose_y=True), scale=dh ** -0.5)
        out = L.matmul(L.softmax(sc), v)
    return m, out


class TestTheJudgesAgree:
    """The pass rewrites a chain exactly where the lowering of the fused
    op would take a kernel: both ask ``KernelSite.on`` and ``path_at``."""

    @pytest.mark.parametrize("axes, batch, seq, want", [
        (None, 8, 128, True),
        (None, 8, 16, False),             # no kernel at this length
        (None, 8, 1024, True),            # jax's flash kernel, called directly
        ({"dp": 4}, 8, 128, True),        # 2 rows a chip, once per shard
        ({"dp": 4}, 6, 128, False),       # the axis does not divide the batch
        ({"dp": 4}, 8, 1024, False),      # only the fused kernel per shard
        ({"dp": 2, "tp": 1}, 8, 512, True),
        ({"dp": 2, "tp": 2}, 8, 128, False),
        ({"tp": 4}, 8, 128, False),
        ("custom rules", 8, 128, True),   # the default one-axis mesh
    ])
    def test_pass_and_lowering(self, monkeypatch, axes, batch, seq, want):
        from types import SimpleNamespace as NS
        from jax.sharding import Mesh
        from paddle_tpu.ops.attention import path_at
        from paddle_tpu.ops.registry import LoweringContext
        from paddle_tpu.parallel import sharding as shd
        m, out = _attention_chain(batch, seq)
        if axes == "custom rules":
            mesh = shd.build_plan(m, mode=_CUSTOM_RULES).mesh
            assert len(mesh.axis_names) == 1 and mesh.devices.size > 1
        elif axes is not None:
            n = int(np.prod(list(axes.values())))
            mesh = Mesh(np.array(jax.devices()[:n]).reshape(
                tuple(axes.values())), tuple(axes))
        else:
            mesh = None
        stats = PassPipeline([create_pass("fuse_attention")]).apply(
            m, targets=[out.name],
            sharding_plan=None if mesh is None else NS(mesh=mesh))
        rewritten = stats["fuse_attention"].get("ops_fused", 0) == 1
        # the lowering's side, on a chip, as fluid/executor.py sets it up
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ctx = LoweringContext()
        ctx.mesh = mesh
        ctx.partitioned = mesh is not None and mesh.devices.size > 1
        q = _sds(batch, 2, seq, 64, dtype=jnp.float32)
        takes_a_kernel = path_at(ctx.kernel_site(q), q, q, q, None, False,
                                 False) != "xla"
        assert rewritten == takes_a_kernel == want


def _sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _lowering_counts():
    return {p: _counter(f"attention.lowering.{p}")
            for p in ("fused_kernel", "flash_kernel", "xla")}


class TestAttentionDispatch:
    """``flash_attention`` picks its path from its site and from shapes
    and dtypes alone, and counts the pick; traced abstractly, so no
    kernel runs."""

    @pytest.mark.parametrize("seq, d, bias, drop, on_chip, want", [
        (512, 64, "row", True, True, "fused_kernel"),
        (512, 64, None, False, True, "fused_kernel"),
        (256, 128, "row", True, True, "fused_kernel"),
        (128, 64, "row", True, True, "fused_kernel"),
        (512, 64, "full", False, True, "xla"),     # a [B,H,S,S] bias
        (512, 64, "full", True, True, "xla"),
        (640, 64, "row", False, True, "xla"),      # past the fused kernel,
        (896, 64, None, False, True, "xla"),       # short of the streamed
        (1024, 64, "row", False, True, "flash_kernel"),
        (1024, 64, "full", False, True, "flash_kernel"),
        (1024, 128, "row", True, True, "xla"),     # flash has no dropout
        (512, 64, "row", True, False, "xla"),      # no site: the CPU, tp
        (1024, 64, None, False, False, "xla"),
    ])
    def test_path_and_counter(self, seq, d, bias, drop, on_chip, want):
        import functools
        from paddle_tpu.ops import attention
        from paddle_tpu.ops.registry import KernelSite
        b, h = 2, 4
        q = _sds(b, h, seq, d)
        mask = {None: None, "row": _sds(b, 1, 1, seq, dtype=jnp.float32),
                "full": _sds(b, h, seq, seq, dtype=jnp.float32)}[bias]
        site = KernelSite() if on_chip else None
        assert attention.path_at(site, q, q, q, mask, False, drop) == want
        if on_chip:
            assert attention.attention_path(q, q, q, mask, False,
                                            drop) == want
        before = _lowering_counts()
        f = functools.partial(
            attention.flash_attention, dropout_rate=0.1 if drop else 0.0,
            dropout_key=jax.random.PRNGKey(0) if drop else None, site=site)
        out = jax.eval_shape(f, q, q, q, mask)
        assert (out.shape, out.dtype) == (q.shape, q.dtype)
        after = _lowering_counts()
        assert {p: after[p] - before[p] for p in after} \
            == {p: int(p == want) for p in after}

    @pytest.mark.parametrize("seq, kv_heads, window, want", [
        (512, 8, 0, "xla"),                # causal, short of the constant
        (896, 8, 0, "xla"),
        (1024, 8, 0, "splash_kernel"),     # at the constant
        (512, 8, 128, "splash_kernel"),    # a window: at every length
        (512, 2, 0, "splash_kernel"),      # grouped heads: likewise
    ])
    def test_where_causal_attention_starts_to_stream(self, seq, kv_heads,
                                                     window, want):
        from paddle_tpu.ops.attention import attention_path
        q, k = _sds(1, 8, seq, 128), _sds(1, kv_heads, seq, 128)
        assert attention_path(q, k, k, None, True, False, window) == want

    def test_causal_and_unaligned_lengths_stay_off_the_fused_kernel(self):
        from paddle_tpu.ops.attention import attention_path
        q = _sds(2, 4, 512, 64)
        assert attention_path(q, q, q, None, True, False) == "xla"
        q = _sds(2, 4, 200, 64)
        assert attention_path(q, q, q, None, False, False) == "xla"

    def test_the_op_lowering_asks_the_context(self, monkeypatch):
        """Inside a partitioned program the op takes XLA on a TPU too."""
        from paddle_tpu.ops.registry import LoweringContext, get_op
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        q = _sds(2, 4, 512, 64)
        for partitioned, want in ((False, "fused_kernel"), (True, "xla")):
            ctx = LoweringContext(base_key=jax.random.PRNGKey(0))
            ctx.partitioned = partitioned
            before = _lowering_counts()
            jax.eval_shape(
                lambda q: get_op("fused_multihead_attention").fn(
                    {"Q": [q], "K": [q], "V": [q]},
                    {"dropout_rate": 0.1, "dropout_seed": 3}, ctx), q)
            assert _lowering_counts()[want] - before[want] == 1


def _counter_bits(seed_ref, head, shape):
    """Stands in for the on-core PRNG, which the CPU interpreter stubs
    with zeros: a counter-based hash of (seed, head, position), so the
    tests see what the kernel's plumbing does with real bits."""
    u32 = jnp.uint32
    r = jax.lax.broadcasted_iota(u32, shape, 0)
    c = jax.lax.broadcasted_iota(u32, shape, 1)
    x = r * u32(shape[1]) + c
    x = x ^ (jnp.asarray(head).astype(u32) * u32(0x9E3779B9))
    x = x ^ (seed_ref[0].astype(u32) * u32(0x85EBCA6B))
    x = (x ^ (x >> 16)) * u32(0x7FEB352D)
    x = (x ^ (x >> 15)) * u32(0x846CA68B)
    return x ^ (x >> 16)


def _attn_operands(b, h, sq, sk, d, dtype=jnp.float32, bias_batch=None):
    ks = jax.random.split(jax.random.PRNGKey(sq + sk + d), 4)
    q = jax.random.normal(ks[0], (b, h, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, h, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, h, sk, d), dtype)
    w = jax.random.normal(ks[3], (b, h, sq, d), dtype)
    bias = None
    if bias_batch:
        # odd batch entries pad their last quarter, as BERT's mask does
        pad = (jnp.arange(sk)[None, :] >= (3 * sk) // 4) \
            & (jnp.arange(bias_batch)[:, None] % 2 == (bias_batch > 1))
        bias = jnp.where(pad, -10000.0, 0.0).astype(jnp.float32)[
            :, None, None]
    return q, k, v, w, bias


def _fwd_and_grads(f, q, k, v, w):
    out, vjp = jax.vjp(f, q, k, v)
    return (out,) + vjp(w)


class TestFusedAttentionKernel:
    @pytest.mark.parametrize("b, h, sq, sk, d, bias_batch", [
        (2, 4, 128, 128, 64, 2),      # two lane groups of two heads
        (2, 4, 256, 256, 32, None),   # four heads a lane group, no bias
        (3, 2, 128, 256, 64, 1),      # one bias row for the whole batch,
                                      # queries shorter than keys
        (1, 2, 128, 128, 128, 1),     # a head is a whole lane group
    ])
    def test_interpret_numerics_against_the_reference(self, b, h, sq, sk,
                                                      d, bias_batch):
        """Forward and the three gradients in float32, dropout off."""
        from jax.experimental.pallas import tpu as pltpu
        from paddle_tpu.ops import pallas_kernels as pk
        from paddle_tpu.ops.attention import _reference_attention
        q, k, v, w, bias = _attn_operands(b, h, sq, sk, d,
                                          bias_batch=bias_batch)
        assert pk.fused_attention_supported(q, k, v, bias)
        scale = d ** -0.5
        with pltpu.force_tpu_interpret_mode():
            got = _fwd_and_grads(
                lambda q, k, v: pk.fused_attention_tpu(q, k, v, bias,
                                                       scale=scale),
                q, k, v, w)
        want = _fwd_and_grads(
            lambda q, k, v: _reference_attention(
                q, k, v, bias, scale, False, 0.0, None, True), q, k, v, w)
        for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-5, atol=2e-5, err_msg=name)

    @pytest.mark.parametrize("upscale", [True, False])
    def test_dropout_is_consistent_with_the_exported_mask(self, upscale,
                                                          monkeypatch):
        """With dropout on: the forward and the backward both apply the
        mask the export kernel shows (same seed, same head index, same
        tiles), the normaliser is the sum of the UNdropped exponentials,
        kept probabilities scale by 1/(1-p) under upscale_in_train."""
        from jax.experimental.pallas import tpu as pltpu
        from paddle_tpu.ops import pallas_kernels as pk
        monkeypatch.setattr(pk, "_head_bits", _counter_bits)
        b, h, s, d, p = 2, 4, 128, 64, 0.25
        q, k, v, w, bias = _attn_operands(b, h, s, s, d, bias_batch=b)
        key = jax.random.PRNGKey(5)
        scale = d ** -0.5
        with pltpu.force_tpu_interpret_mode():
            keep = pk.fused_attention_keep_mask(q.shape, s, p, key)
            other = pk.fused_attention_keep_mask(q.shape, s, p,
                                                 jax.random.PRNGKey(6))
            got = _fwd_and_grads(
                lambda q, k, v: pk.fused_attention_tpu(
                    q, k, v, bias, scale=scale, dropout_rate=p,
                    dropout_key=key, dropout_upscale=upscale), q, k, v, w)
        keep = np.asarray(keep)
        assert keep.shape == (b, h, s, s) and keep.dtype == np.uint8
        assert abs(keep.mean() - (1 - p)) < 0.01
        # every head draws its own bits, and another key other bits
        flat = keep.reshape(b * h, -1)
        assert len({row.tobytes() for row in flat}) == b * h
        assert (np.asarray(other) != keep).mean() > 0.2

        def ref(q, k, v):
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale + bias
            pr = jax.nn.softmax(sc, -1) * keep
            if upscale:
                pr = pr / (1 - p)
            return jnp.einsum("bhqk,bhkd->bhqd", pr, v)
        want = _fwd_and_grads(ref, q, k, v, w)
        for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-5, atol=2e-5, err_msg=name)

    def test_infer_time_downgrade_scales_the_probabilities(self):
        """downgrade_in_infer at test time: no mask, probabilities times
        (1 - p), through the op's lowering arguments."""
        from jax.experimental.pallas import tpu as pltpu
        from paddle_tpu.ops import pallas_kernels as pk
        q, k, v, _, bias = _attn_operands(1, 2, 128, 128, 64, bias_batch=1)
        with pltpu.force_tpu_interpret_mode():
            plain = pk.fused_attention_tpu(q, k, v, bias)
            scaled = pk.fused_attention_tpu(q, k, v, bias, dropout_rate=0.1,
                                            prob_scale=0.9)
        np.testing.assert_allclose(np.asarray(scaled),
                                   0.9 * np.asarray(plain), rtol=1e-6)

    def test_supported_shapes(self):
        from paddle_tpu.ops import pallas_kernels as pk
        q = _sds(4, 12, 512, 64)
        row = _sds(4, 1, 1, 512, dtype=jnp.float32)
        assert pk.fused_attention_supported(q, q, q, row)
        assert pk.fused_attention_supported(q, q, q, None)
        assert pk.fused_attention_supported(
            q, q, q, _sds(1, 1, 1, 512, dtype=jnp.float32))
        # a bias over heads or query rows would sit in HBM at score size
        assert not pk.fused_attention_supported(
            q, q, q, _sds(4, 12, 512, 512, dtype=jnp.float32))
        assert not pk.fused_attention_supported(
            q, q, q, _sds(4, 1, 512, 512, dtype=jnp.float32))
        long = _sds(4, 12, 1024, 64)
        assert not pk.fused_attention_supported(long, long, long, None)
        odd = _sds(4, 12, 200, 64)
        assert not pk.fused_attention_supported(odd, odd, odd, None)
        assert not pk.fused_attention_supported(
            q, q, _sds(4, 12, 512, 64, dtype=jnp.float32), None)
        # a head width that tiles the 128 lanes, in whole lane groups
        assert pk.fused_attention_supported(*[_sds(4, 8, 512, 32)] * 3)
        assert pk.fused_attention_supported(*[_sds(4, 3, 512, 128)] * 3)
        assert not pk.fused_attention_supported(*[_sds(4, 12, 512, 40)] * 3)
        assert not pk.fused_attention_supported(*[_sds(4, 5, 512, 64)] * 3)
        # heads per grid step: whole lane groups that divide the head count
        assert pk._heads_per_step(12, 128, 64) == 12
        assert pk._heads_per_step(12, 512, 64) == 6
        assert pk._heads_per_step(16, 512, 64) == 8
        assert pk._heads_per_step(10, 512, 64) == 2
        assert pk._heads_per_step(16, 512, 32) == 8
        assert pk._heads_per_step(3, 512, 128) == 3

    @pytest.mark.parametrize("b, h, s, d", [(32, 12, 512, 64),
                                            (128, 12, 128, 64)])
    def test_mosaic_preflight_at_berts_widths(self, b, h, s, d):
        """bf16, the padding bias as its [B, 1, 1, S] row, dropout 0.1,
        forward and backward through the op's own dispatch, compiled for a
        described v5e: both kernels are there and no [B, H, S, S] array
        exists outside them."""
        import functools
        from paddle_tpu.ops.attention import flash_attention
        from paddle_tpu.ops.pallas_preflight import (compile_for_tpu,
                                                     mosaic_call_count)
        from paddle_tpu.ops.registry import KernelSite
        q = _sds(b, h, s, d)
        bias = _sds(b, 1, 1, s, dtype=jnp.float32)
        key = jax.random.PRNGKey(0)

        def step(q, k, v, bias, key, w):
            attend = functools.partial(
                flash_attention, mask=bias, dropout_rate=0.1,
                dropout_key=key, site=KernelSite())
            return _fwd_and_grads(attend, q, k, v, w)
        compiled = compile_for_tpu(step, q, q, q, bias, key, q)
        assert mosaic_call_count(compiled) == 2
        assert f"[{b},{h},{s},{s}]" not in compiled.as_text()


def _counter_keep(seed_ref, shape, threshold):
    """``_keep_mask`` with ``_counter_bits`` for the on-core PRNG: seeded,
    as the kernel seeds it, from (op seed, grid position)."""
    from jax.experimental import pallas as pl
    return _counter_bits(seed_ref, pl.program_id(0), shape) \
        >= jnp.uint32(threshold)


class TestKernelsPerShard:
    """The dropout family and the attention op through their lowerings in
    a context partitioned over a 4-device data axis (the TPU interpreter,
    a counter-based generator for the on-core PRNG): every shard runs the
    kernel on its own rows with its own stream, the one the unpartitioned
    kernel draws from the op's key with the shard's index folded in,
    forward and backward alike."""

    SHARDS, RATE, SEED = 4, 0.25, 11

    @pytest.fixture
    def ctx(self, monkeypatch):
        from jax.sharding import Mesh
        from paddle_tpu.ops import pallas_kernels as pk
        from paddle_tpu.ops import registry
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(pk, "_keep_mask", _counter_keep)
        monkeypatch.setattr(pk, "_head_bits", _counter_bits)
        ctx = registry.LoweringContext(base_key=jax.random.PRNGKey(3))
        ctx.mesh = Mesh(np.array(jax.devices()[:self.SHARDS]), ("dp",))
        ctx.partitioned = True
        # the jitted per-shard calls outlive a test: none traced with
        # another PRNG comes in, none traced with this one stays
        registry._per_shard.cache_clear()
        yield ctx
        registry._per_shard.cache_clear()

    def _shard_key(self, ctx, i):
        return jax.random.fold_in(ctx.key_for(self.SEED), i)

    @staticmethod
    def _run(f, *args):
        """``f(*args)`` as ONE program, read back when it is done.  The
        interpreter's callbacks start small programs of their own on device
        0; an eager op of the test's, queued there behind the running
        kernels whose output it waits for, would stand in their way for
        good."""
        out = jax.block_until_ready(jax.jit(f)(*args))
        return [np.asarray(a) for a in out]

    @pytest.mark.parametrize("op_type, slots", [
        ("dropout", ("X",)),
        ("fused_dropout_add", ("X", "Residual")),
        ("fused_act_dropout", ("X",)),
    ])
    def test_dropout_family(self, ctx, op_type, slots):
        from jax.experimental.pallas import tpu as pltpu
        from paddle_tpu.ops import pallas_kernels as pk
        from paddle_tpu.ops.registry import get_op
        n, rows = self.SHARDS, 64
        # every shard holds the same rows: what differs is the mask
        one = [jnp.abs(jax.random.normal(jax.random.PRNGKey(i),
                                         (rows, 128))) + 1.0
               for i in range(len(slots))]
        xs = [jnp.tile(x, (n, 1)) for x in one]
        attrs = {"dropout_prob": self.RATE, "op_seed": self.SEED,
                 "dropout_implementation": "upscale_in_train",
                 "act": "relu"}
        direct = {
            "dropout": lambda key, x: pk.fused_dropout_tpu(
                x, key, self.RATE, True)[0],
            "fused_dropout_add": lambda key, x, r: pk.fused_dropout_add_tpu(
                x, r, key, self.RATE, True),
            "fused_act_dropout": lambda key, x: pk.fused_act_dropout_tpu(
                x, key, self.RATE, True, "relu"),
        }[op_type]

        def lowered(*xs):
            return get_op(op_type).fn(
                {s: [x] for s, x in zip(slots, xs)}, attrs, ctx)["Out"][0]

        def ones_vjp(f, *xs):
            out, vjp = jax.vjp(f, *xs)
            return (out,) + vjp(jnp.ones_like(out))

        calls = _counter("kernel.shard_map_calls")
        with pltpu.force_tpu_interpret_mode():
            got = self._run(functools.partial(ones_vjp, lowered), *xs)
            want = [self._run(functools.partial(
                ones_vjp, functools.partial(direct, self._shard_key(ctx, i))),
                *one) for i in range(n)]
        assert _counter("kernel.shard_map_calls") - calls == 1
        got = [a.reshape(n, rows, 128) for a in got]
        for i in range(n):
            for a, w in zip(got, want[i]):
                np.testing.assert_array_equal(a[i], np.asarray(w))
        # x > 0 everywhere: a zero of the gradient is a dropped element
        keep = got[1] != 0
        assert abs(keep.mean() - (1 - self.RATE)) < 0.02
        for i in range(n):
            for j in range(i):
                assert (keep[i] != keep[j]).mean() > 0.2, (i, j)
        if op_type == "dropout":
            # the forward dropped what the backward drops
            np.testing.assert_array_equal(got[0] != 0, keep)

    def test_attention(self, ctx):
        from jax.experimental.pallas import tpu as pltpu
        from paddle_tpu.ops import pallas_kernels as pk
        from paddle_tpu.ops.registry import get_op
        n, h, s, d = self.SHARDS, 2, 128, 64
        q1, k1, v1, w1, bias1 = _attn_operands(1, h, s, s, d, bias_batch=1)
        q, k, v, w, bias = (jnp.tile(a, (n, 1, 1, 1))
                            for a in (q1, k1, v1, w1, bias1))
        attrs = {"scale": d ** -0.5, "dropout_rate": self.RATE,
                 "dropout_seed": self.SEED,
                 "dropout_implementation": "upscale_in_train"}

        def lowered(bias):
            return lambda q, k, v: get_op("fused_multihead_attention").fn(
                {"Q": [q], "K": [k], "V": [v], "Mask": [bias]}, attrs,
                ctx)["Out"][0]

        before = _lowering_counts()
        calls = _counter("kernel.shard_map_calls")
        def direct(i, q, k, v):
            return pk.fused_attention_tpu(
                q, k, v, bias1, scale=d ** -0.5, dropout_rate=self.RATE,
                dropout_key=self._shard_key(ctx, i))

        def run(f, *args):
            return self._run(functools.partial(_fwd_and_grads, f), *args)

        with pltpu.force_tpu_interpret_mode():
            got = run(lowered(bias), q, k, v, w)
            # a [1, 1, 1, S] bias row goes to every shard whole
            shared = run(lowered(bias1), q, k, v, w)
            want = [run(functools.partial(direct, i), q1, k1, v1, w1)
                    for i in range(n)]
        assert _lowering_counts()["fused_kernel"] \
            - before["fused_kernel"] == 2
        assert _lowering_counts()["xla"] == before["xla"]
        assert _counter("kernel.shard_map_calls") - calls == 2
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, shared):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
            for i in range(n):
                np.testing.assert_array_equal(
                    np.asarray(a)[i], np.asarray(want[i][
                        ("out", "dq", "dk", "dv").index(name)])[0],
                    err_msg=f"{name}, shard {i}")
        out = np.asarray(got[0])
        for i in range(n):
            for j in range(i):
                assert not np.allclose(out[i], out[j], atol=1e-3), (i, j)

    def test_other_kernels_keep_their_xla_lowering(self, ctx):
        """Only what a cell has run per shard runs per shard: a causal
        sequence long enough for the splash kernel stays on XLA in a
        partitioned program."""
        from paddle_tpu.ops.attention import flash_attention
        from paddle_tpu.ops.registry import KernelSite
        q = _sds(4, 4, 1024, 128)
        for site, want in ((ctx.kernel_site(q), "xla"),
                           (KernelSite(), "splash_kernel")):
            before = _counter(f"attention.lowering.{want}")
            jax.eval_shape(
                lambda q: flash_attention(q, q, q, causal=True,
                                          site=site), q)
            assert _counter(f"attention.lowering.{want}") - before == 1


class TestBertStepKeepsTheScoresOnTheCore:
    """The whole seq-512 training step as the benchmark's cell compiles it
    (Program -> default passes + AMP -> Executor's step function, batch 32,
    BERT-base widths; two layers, a layer is a layer), for a described v5e:
    every attention is the fused kernel, forward and backward, and no
    [32, 12, 512, 512] array exists in the executable outside the custom
    calls."""

    def test_no_score_sized_buffer_in_the_step(self, monkeypatch):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid.core import Scope, scope_guard
        from paddle_tpu.fluid.framework import reset_unique_name
        from paddle_tpu.models.static_graphs import (
            bert_demo_feed, build_bert_train_program)
        from paddle_tpu.ops.pallas_preflight import (compile_for_tpu,
                                                     mosaic_call_count)
        from paddle_tpu.ops.registry import LoweringContext
        # the lowerings ask jax.default_backend(); the target is the TPU
        monkeypatch.setattr(LoweringContext, "pallas_ok",
                            lambda self: not self.partitioned)
        batch, seq, layers = 32, 512, 2
        reset_unique_name()
        main, startup, loss = build_bert_train_program(
            vocab=30522, hidden=768, heads=12, seq=seq, layers=layers,
            dropout=0.1)
        bs = fluid.BuildStrategy()
        bs.amp = True
        program = fluid.CompiledProgram(main, build_strategy=bs)
        feed = bert_demo_feed(np.random.RandomState(0), batch=batch,
                              seq=seq, vocab=30522)
        before = _lowering_counts()
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            program._apply_ir_passes([loss.name])
            scope = fluid.global_scope()
            step = exe._prepare(main, feed, [loss.name], scope, plan=None)
            mut = {n: scope.find_var(n) for n in step.param_names
                   if n in step.written_names}
            ro = {n: scope.find_var(n) for n in step.param_names
                  if n not in step.written_names}
            compiled = compile_for_tpu(step.raw_fn, mut, ro, feed,
                                       jax.random.PRNGKey(0))
        ops = [op.type for op in main.global_block().ops]
        assert ops.count("fused_multihead_attention") == layers
        assert "softmax" not in ops
        # once per layer: the grad op applies the vjp the forward op kept
        after = _lowering_counts()
        assert after["fused_kernel"] - before["fused_kernel"] == layers
        assert after["xla"] == before["xla"]
        text = compiled.as_text()
        assert mosaic_call_count(compiled) >= 2 * layers
        assert f"[{batch},12,{seq},{seq}]" not in text


class TestCausalDecoderStepKeepsTheScoresOnTheCore:
    """The benchmark's Mellum2 configuration (benchmark/configs/
    mellum2_12b_a2_5b_train: window and full causal layers, grouped heads,
    sparse experts), shrunk in everything but the mechanisms."""

    def test_no_score_sized_buffer_in_the_step(self, monkeypatch):
        """The training step at a sequence of 2048 (window 1024, 8:1 heads of
        128, experts in whole kernel tiles), Program -> default passes + AMP ->
        the Executor's step function, compiled for a described v5e: every
        attention is the splash kernel, every grouped matmul the megablox
        kernel, and no [.., 2048, 2048] array exists outside the custom calls."""
        from paddle_tpu.ops.pallas_preflight import (compile_for_tpu,
                                                     mosaic_call_count)
        from paddle_tpu.ops.registry import LoweringContext
        monkeypatch.setattr(LoweringContext, "pallas_ok",
                            lambda self: not self.partitioned)
        import os
        from paddle_tpu.fluid import trace
        from paddle_tpu.fluid.core import Scope, scope_guard
        from benchmark.harness.registry import Registry, load_module
        from benchmark.harness.strategy import build_strategy
        REG = Registry()
        cfg, cfg_dir = REG.config("mellum2_12b_a2_5b_train")
        mix = REG.mix("causal_lm_seq8192")
        model = load_module(os.path.join(cfg_dir, "model.py"))
        seq = 2048
        cfg.update({"hidden_size": 256, "num_attention_heads": 8,
                    "num_key_value_heads": 1, "vocab_size": 512,
                    "moe_intermediate_size": 128, "num_experts": 2,
                    "num_experts_per_tok": 2, "num_hidden_layers": 4,
                    "published": dict(cfg["published"], num_experts=8)})
        cfg["layer_types"] = ["sliding_attention", "full_attention"] * 2
        cfg["num_hidden_layers"] = 2
        mix.update({"seq_len": seq, "samples_per_chip": 1})
        kind = REG.module("traffic_kinds", mix["kind"] + ".py")
        feed = kind.generate(mix, cfg, 0, 1, n_batches=1)[0]

        def count(name):
            return trace.metrics().counter(name).value
        names = ("attention.lowering.splash_kernel.window",
                 "attention.lowering.splash_kernel.full_causal",
                 "attention.lowering.xla", "moe.gmm_lowering.megablox",
                 "moe.gmm_lowering.ragged_dot")
        reset_unique_name()
        built = model.build(cfg, mix, train=True)
        program = fluid.CompiledProgram(built["main"],
                                        build_strategy=build_strategy(cfg, mix))
        before = {n: count(n) for n in names}   # after the build's shape inference
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(built["startup"])
            program._apply_ir_passes([built["loss"].name])
            scope = fluid.global_scope()
            step = exe._prepare(built["main"], feed, [built["loss"].name],
                                scope, plan=None)
            mut = {n: scope.find_var(n) for n in step.param_names
                   if n in step.written_names}
            ro = {n: scope.find_var(n) for n in step.param_names
                  if n not in step.written_names}
            compiled = compile_for_tpu(step.raw_fn, mut, ro, feed,
                                       jax.random.PRNGKey(0))
        moved = {n: count(n) - before[n] for n in names}
        # each op lowered once: its grad op applies the vjp it kept
        assert moved["attention.lowering.splash_kernel.window"] == 1
        assert moved["attention.lowering.splash_kernel.full_causal"] == 1
        assert moved["attention.lowering.xla"] == 0
        assert moved["moe.gmm_lowering.megablox"] == 2 * 3
        assert moved["moe.gmm_lowering.ragged_dot"] == 0
        text = compiled.as_text()
        assert mosaic_call_count(compiled) >= 2 * (3 + 3 * 3)
        assert f"{seq},{seq}]" not in text
        exe.close()


    @pytest.mark.parametrize("k, n", [(2304, 896), (896, 2304)])
    def test_grouped_matmul_compiles_at_the_cells_widths(self, k, n):
        """bf16, 16 experts held, forward and both backward kernels with
        their own tile plans, for a described v5e (scoped VMEM included)."""
        from paddle_tpu.ops.pallas_preflight import (compile_for_tpu,
                                                     mosaic_call_count)
        from paddle_tpu.parallel import moe
        x = _sds(8192, k)
        w = _sds(16, k, n)
        sizes = jax.ShapeDtypeStruct((16,), jnp.int32)

        def step(x, w, sizes, g):
            out, vjp = jax.vjp(
                lambda x, w: moe.grouped_matmul(x, w, sizes, True), x, w)
            return (out,) + vjp(g)
        compiled = compile_for_tpu(step, x, w, sizes, _sds(8192, n))
        assert mosaic_call_count(compiled) == 3
        assert (moe._tile(2304), moe._tile(896)) == (768, 896)


    @pytest.mark.parametrize("tokens, top_k, width, rows", [
        (8192, 8, 2304, 65536),       # mellum2_train_seq8192
        (16384, 8, 2048, 32768),      # keye_vl2_train_seq16384 (max_rows)
        (4096, 4, 3584, 16384)])      # xing4_train_seq4096
    def test_the_permutation_compiles_at_the_cells_shapes(self, tokens,
                                                           top_k, width,
                                                           rows):
        """``moe_combine`` forward with its gradient and ``moe_dispatch``'s
        gradient for a described v5e: the segment-sum kernel (scoped VMEM
        included) once each way, and no array with a row per assignment."""
        from paddle_tpu.ops.pallas_preflight import (compile_for_tpu,
                                                     mosaic_call_count)
        from paddle_tpu.parallel import moe
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        plan = moe.Plan(i32(rows), i32(tokens, top_k), i32(16))

        def step(x, y, w, plan, g):
            out, vjp = jax.vjp(
                lambda x, y, w: moe.combine(
                    y + moe.dispatch(x, plan, True), w, plan, True), x, y, w)
            return (out,) + vjp(g)
        compiled = compile_for_tpu(
            step, _sds(tokens, width), _sds(rows, width),
            jax.ShapeDtypeStruct((tokens, top_k), jnp.float32), plan,
            _sds(tokens, width))
        assert mosaic_call_count(compiled) == 2
        assert f"[{tokens},{top_k},{width}]" not in compiled.as_text()


# ---------------------------------------------------------------------------
# fuse_sparse_embedding
# ---------------------------------------------------------------------------

class TestFuseSparseEmbedding:
    def test_ctr_train_rewrite_bit_parity(self):
        rng = np.random.RandomState(0)
        feed = ctr_demo_feed(rng)
        l_off, p_off = _train(*build_ctr_train_program(), feed)
        reset_unique_name()
        r0 = _counter("kernel_tier.fuse_sparse_embedding.rewrites")
        m, s, loss = build_ctr_train_program()
        l_on, p_on = _train(m, s, loss, feed,
                            build=_tier_bs(fuse_sparse_embedding=True))
        assert _counter(
            "kernel_tier.fuse_sparse_embedding.rewrites") - r0 == 4
        types = _op_types(m)
        assert types.count("fused_embedding_pool") == 4
        assert "lookup_table_v2" not in types
        assert l_on == l_off
        for name in p_off:
            assert np.array_equal(p_off[name], p_on[name]), name

    @pytest.mark.parametrize("pool", ["sum", "average"])
    def test_length_masked_pool_parity(self, pool):
        def build():
            m, s = fluid.Program(), fluid.Program()
            with fluid.program_guard(m, s):
                ids = fluid.data("ids", [-1, 6], dtype="int64")
                ln = fluid.data("ln", [-1], dtype="int64")
                emb = L.embedding(ids, size=[64, 8])
                p = L.sequence_pool(emb, pool, length=ln)
                loss = L.mean(L.fc(p, 4))
                fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
            return m, s, loss

        rng = np.random.RandomState(2)
        feed = {"ids": rng.randint(0, 64, (5, 6)).astype("int64"),
                "ln": np.array([6, 3, 1, 5, 2], "int64")}
        l_off, p_off = _train(*build(), feed, n=6)
        reset_unique_name()
        m, s, loss = build()
        l_on, p_on = _train(m, s, loss, feed, n=6,
                            build=_tier_bs(fuse_sparse_embedding=True))
        op = next(o for o in m.global_block().ops
                  if o.type == "fused_embedding_pool")
        assert "Length" in op.inputs
        np.testing.assert_allclose(l_on, l_off, rtol=1e-6, atol=1e-7)
        for name in p_off:
            np.testing.assert_allclose(p_on[name], p_off[name],
                                       rtol=1e-6, atol=1e-7)

    def test_reduce_sum_spelling_fuses(self):
        m, s = fluid.Program(), fluid.Program()
        with fluid.program_guard(m, s):
            ids = fluid.data("ids", [-1, 4], dtype="int64")
            emb = L.embedding(ids, size=[32, 8])
            out = L.reduce_sum(emb, dim=1)
        stats = PassPipeline([create_pass("fuse_sparse_embedding")]).apply(
            m, targets=[out.name])
        assert stats["fuse_sparse_embedding"]["ops_fused"] == 1
        assert "fused_embedding_pool" in _op_types(m)

    def test_multi_consumer_embedding_declines(self):
        """The gathered [B,S,D] tensor feeds a second consumer — the
        whole point of the fusion is to never materialise it, so the
        rewrite must leave the chain alone."""
        m, s = fluid.Program(), fluid.Program()
        with fluid.program_guard(m, s):
            ids = fluid.data("ids", [-1, 4], dtype="int64")
            emb = L.embedding(ids, size=[32, 8])
            pooled = L.sequence_pool(emb, "sum")
            flat = L.reshape(emb, [-1, 32])      # second consumer
        stats = PassPipeline([create_pass("fuse_sparse_embedding")]).apply(
            m, targets=[pooled.name, flat.name])
        assert stats["fuse_sparse_embedding"].get("ops_fused", 0) == 0


# ---------------------------------------------------------------------------
# what went with the switches, the pipeline's order, and satellites
# ---------------------------------------------------------------------------

def _mlp(optimizer):
    m, s = fluid.Program(), fluid.Program()
    with fluid.program_guard(m, s):
        x = fluid.data("x", [-1, 16])
        y = fluid.data("y", [-1, 1], dtype="int64")
        h = L.fc(x, 32, act="relu")
        h = L.fc(h, 16, act="relu")
        logits = L.fc(h, 10)
        loss = L.mean(L.softmax_with_cross_entropy(logits, y))
        optimizer().minimize(loss)
    return m, s, loss


class TestNoSwitchDecidesWhereAKernelRuns:
    @pytest.mark.parametrize("field", ["fuse_attention", "kernel_tier",
                                       "fuse_optimizer"])
    def test_build_strategy_has_no_such_field(self, field):
        assert not hasattr(fluid.BuildStrategy(), field)

    def test_no_crossover_flag(self):
        assert not [k for k in fluid.core._FLAGS if k.startswith("pallas")]

    @pytest.mark.parametrize("optimizer", [
        lambda: fluid.optimizer.AdamOptimizer(1e-2),
        lambda: fluid.optimizer.MomentumOptimizer(0.05, 0.9)],
        ids=["adam", "momentum"])
    def test_fuse_all_optimizer_ops_is_a_parity_field(self, optimizer):
        """Upstream scripts set it; it selects no pass and the program
        stays op for op (XLA fuses each update into its dW matmul)."""
        from paddle_tpu.fluid.passes import passes_for_build_strategy
        bs = _tier_bs(fuse_all_optimizer_ops=True)
        assert [p.name for p in passes_for_build_strategy(bs)] \
            == ["fuse_attention"]
        m, _, loss = _mlp(optimizer)
        before = _op_types(m)
        assert _default_pipeline(m, loss, fuse_all_optimizer_ops=True) == 0
        assert _op_types(m) == before

    def test_canonical_order_with_amp(self):
        bs = _tier_bs(fuse_sparse_embedding=True, amp=True, enable_dce=True,
                      fuse_elewise_add_act_ops=True)
        from paddle_tpu.fluid.passes import passes_for_build_strategy
        names = [p.name for p in passes_for_build_strategy(bs)]
        assert names.index("fuse_elewise_add_act") \
            < names.index("fuse_attention") \
            < names.index("fuse_sparse_embedding") \
            < names.index("amp_bf16") < names.index("dce")


def test_the_demo_programs_mask_is_berts_bias_row():
    """(m - 1) * 10000: 0 where attended, -10000 where padded."""
    m, s, _ = build_bert_train_program(layers=1)
    scale = next(o for o in m.global_block().ops if o.type == "scale")
    feed = bert_demo_feed(np.random.RandomState(0))
    with scope_guard(Scope()):
        ex = fluid.Executor()
        ex.run(s)
        bias, = ex.run(m, feed=feed, fetch_list=[scale.outputs["Out"][0]])
    want = np.where(feed["attn_mask"] > 0, 0.0, -10000.0)
    assert np.array_equal(np.asarray(bias)[:, 0, 0, :], want)
    assert (want == -10000.0).any() and (want == 0.0).any()


class TestSatellites:
    def test_bias_broadcastable_gate(self):
        from paddle_tpu.ops.attention import _bias_broadcastable
        q = jnp.zeros((2, 4, 16, 8))
        k = jnp.zeros((2, 4, 16, 8))
        assert _bias_broadcastable(jnp.zeros((2, 1, 1, 16)), q, k)
        assert _bias_broadcastable(jnp.zeros((1, 4, 16, 16)), q, k)
        assert not _bias_broadcastable(jnp.zeros((2, 16)), q, k)
        assert not _bias_broadcastable(jnp.zeros((2, 3, 1, 16)), q, k)
        assert not _bias_broadcastable(None, q, k)

    def test_embedding_kernels_interpret_numerics(self):
        """The Pallas gather+pool / scatter-add kernels, through their
        public wrappers in TPU interpret mode, against the XLA reference
        (no TPU required).  Batch 5 is not a whole 8-row block and row 0
        repeats an id, so the padding and the read-modify-write paths
        both run."""
        from jax.experimental.pallas import tpu as pltpu
        from paddle_tpu.ops import pallas_kernels as pk
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(64, 128).astype("float32"))
        ids_np = rng.randint(0, 64, (5, 6)).astype("int32")
        ids_np[0, :3] = 7
        ids = jnp.asarray(ids_np)
        wgt = jnp.asarray(rng.rand(5, 6).astype("float32"))
        g = jnp.asarray(rng.randn(5, 128).astype("float32"))
        with pltpu.force_tpu_interpret_mode():
            fwd = pk.fused_embedding_pool_tpu(w, ids, wgt)
            bwd = pk.embedding_pool_grad_tpu(g, ids, wgt, 64)
        want = jnp.einsum("bsd,bs->bd", jnp.take(w, ids, axis=0), wgt)
        np.testing.assert_allclose(np.asarray(fwd), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        rows = g[:, None, :] * wgt[:, :, None]
        want_b = jax.ops.segment_sum(rows.reshape(-1, 128),
                                     ids.reshape(-1), num_segments=64)
        np.testing.assert_allclose(np.asarray(bwd), np.asarray(want_b),
                                   rtol=1e-5, atol=1e-5)

    def test_new_kernels_pass_mosaic_preflight(self):
        """Every pallas_call in the fused embedding kernels compiles
        through Mosaic offline."""
        from paddle_tpu.ops import pallas_kernels as pk
        from paddle_tpu.ops.pallas_preflight import assert_mosaic_lowerable
        w = jnp.zeros((64, 128), jnp.float32)
        ids = jnp.zeros((2, 4), jnp.int32)
        wgt = jnp.ones((2, 4), jnp.float32)
        g = jnp.zeros((2, 128), jnp.float32)
        assert_mosaic_lowerable(pk.fused_embedding_pool_tpu, w, ids, wgt)
        assert_mosaic_lowerable(
            lambda g_, i_, w_: pk.embedding_pool_grad_tpu(g_, i_, w_, 64),
            g, ids, wgt)

    def test_kernels_are_off_inside_a_partitioned_program(self,
                                                           monkeypatch):
        """Mosaic calls cannot be partitioned by GSPMD: a lowering takes
        its kernel on the tpu backend only outside a partitioned
        program."""
        from paddle_tpu.ops.registry import LoweringContext
        ctx = LoweringContext()
        assert not ctx.pallas_ok()                  # cpu backend
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert ctx.pallas_ok()
        ctx.partitioned = True
        assert not ctx.pallas_ok()

    def test_table_beyond_vmem_takes_the_xla_lowering(self):
        """The embedding kernels hold the table in VMEM whole; an 8MB
        table is past that gate and stays on XLA's take/segment_sum."""
        from paddle_tpu.ops import pallas_kernels as pk
        ids = jnp.zeros((2, 4), jnp.int32)
        assert pk.fused_embedding_pool_supported(
            jnp.zeros((8192, 128), jnp.float32), ids)          # 4MB
        assert not pk.fused_embedding_pool_supported(
            jnp.zeros((16384, 128), jnp.float32), ids)         # 8MB

    def test_block_rows_alignment(self):
        """Row blocks are the whole array or a multiple of the widest
        sublane tile, whatever the row count's factors."""
        from paddle_tpu.ops import pallas_kernels as pk
        assert pk._block_rows(5, 1024) == 5
        for m in (107_417, 8192, 98_304, 33):
            bm = pk._block_rows(m, 8 * 1024 * 4)
            assert bm == m or bm % 32 == 0
            assert 2 * bm * 8 * 1024 * 4 <= 8 << 20


# ---------------------------------------------------------------------------
# fuse_paged_attention
# ---------------------------------------------------------------------------

def _paged_chain_program(mask_bias_ok=True):
    """Hand-built copy of the paged decode attend chain
    (serving/decode.py build_paged): gather×2 → reshape×2 →
    mul+reduce_sum → scale → exact-zero mask → softmax →
    mul+reduce_sum."""
    m, s = fluid.Program(), fluid.Program()
    with fluid.program_guard(m, s):
        q = fluid.data("q", [-1, 8])
        kp = fluid.data("kp", [40, 8])
        vp = fluid.data("vp", [40, 8])
        pt = fluid.data("pt", [-1, 16], dtype="int32")
        valid = fluid.data("valid", [-1, 16])
        pti = L.reshape(pt, [-1])
        kg = L.reshape(L.gather(kp, pti), [-1, 16, 8])
        vg = L.reshape(L.gather(vp, pti), [-1, 16, 8])
        sc = L.reduce_sum(kg * L.unsqueeze(q, [1]), dim=[2])
        sc = L.scale(sc, scale=0.25)
        bias = -1e30 if mask_bias_ok else 0.0
        sc = sc * valid + L.scale(valid, scale=1e30, bias=bias)
        p = L.softmax(sc)
        out = L.reduce_sum(vg * L.unsqueeze(p, [2]), dim=[1])
    return m, out


class TestFusePagedAttention:
    def _run(self, prog, out_name, feed):
        ex = fluid.Executor()
        with scope_guard(Scope()):
            return np.asarray(
                ex.run(prog, feed=feed, fetch_list=[out_name])[0])

    def _feed(self, rng, b=3):
        pt = np.zeros((b, 16), np.int32)
        for i in range(b):
            pt[i] = np.arange(16) % 40
        valid = np.zeros((b, 16), np.float32)
        valid[:, :5] = 1.0
        return {"q": rng.randn(b, 8).astype("float32"),
                "kp": rng.randn(40, 8).astype("float32"),
                "vp": rng.randn(40, 8).astype("float32"),
                "pt": pt, "valid": valid}

    def test_rewrite_counts_and_bit_parity(self):
        """The chain rewrites to ONE paged_attention op and the fused
        CPU fallback is bit-identical to the unfused chain — the
        rewrite must be invisible to the decode exactness gate."""
        rng = np.random.RandomState(3)
        feed = self._feed(rng)
        prog, out = _paged_chain_program()
        ref = self._run(prog, out.name, feed)
        r0 = _counter("kernel_tier.fuse_paged_attention.rewrites")
        from paddle_tpu.fluid.passes import PassPipeline, create_pass
        stats = PassPipeline([create_pass("fuse_paged_attention")]).apply(
            prog, targets=[out.name])
        assert _counter(
            "kernel_tier.fuse_paged_attention.rewrites") - r0 == 1
        types = _op_types(prog)
        assert types.count("paged_attention") == 1
        assert "softmax" not in types and "gather" not in types
        fused = self._run(prog, out.name, feed)
        assert np.array_equal(ref, fused)

    def test_build_strategy_knob(self):
        """fuse_paged_attention=False leaves the chain alone; the knob
        (and the kernel_tier umbrella) selects the pass."""
        from paddle_tpu.fluid.passes.builtin import \
            passes_for_build_strategy
        names = [p.name for p in passes_for_build_strategy(
            _tier_bs(fuse_paged_attention=True))]
        assert "fuse_paged_attention" in names
        names_off = [p.name for p in passes_for_build_strategy(
            _tier_bs())]
        assert "fuse_paged_attention" not in names_off

    def test_negative_wrong_mask_bias(self):
        """A mask add whose bias is NOT -scale is not the exact-zero
        decode spelling — the pattern must not fire."""
        prog, out = _paged_chain_program(mask_bias_ok=False)
        from paddle_tpu.fluid.passes import PassPipeline, create_pass
        PassPipeline([create_pass("fuse_paged_attention")]).apply(
            prog, targets=[out.name])
        assert "paged_attention" not in _op_types(prog)
        assert "softmax" in _op_types(prog)

    def test_negative_protected_intermediate(self):
        """A fetched (protected) probability tensor pins the chain: the
        rewrite would delete the fetch target, so it must decline."""
        prog, out = _paged_chain_program()
        sm_out = next(op.outputs["Out"][0]
                      for op in prog.global_block().ops
                      if op.type == "softmax")
        from paddle_tpu.fluid.passes import PassPipeline, create_pass
        PassPipeline([create_pass("fuse_paged_attention")]).apply(
            prog, targets=[out.name, sm_out])
        assert "paged_attention" not in _op_types(prog)

    def test_demo_decode_programs_fuse(self):
        """The real serving/decode.py paged + verify programs rewrite
        (one fused op per unrolled step) and carry the page size from
        the program hint."""
        from paddle_tpu.fluid.passes import PassPipeline, create_pass
        from paddle_tpu.serving import decode as dec
        model = dec.build_demo_decode_model(vocab=13, d_model=8,
                                            max_len=16, seed=2,
                                            page_size=4)
        prog, _ = model.paged_program(40)
        vprog, _ = model.verify_program(40, 3)
        pipe = PassPipeline([create_pass("fuse_paged_attention")])
        pipe.apply(prog, targets=list(prog._hints["fetch_names"]))
        pipe.apply(vprog, targets=list(vprog._hints["fetch_names"]))
        assert _op_types(prog).count("paged_attention") == 1
        assert _op_types(vprog).count("paged_attention") == 3
        pa = next(op for op in prog.global_block().ops
                  if op.type == "paged_attention")
        assert pa.attrs["page_size"] == 4
        assert pa.attrs["neg"] == pytest.approx(1e30)

    @pytest.mark.parametrize("page_size", [8, 1])
    def test_paged_kernel_interpret_numerics(self, page_size):
        """The paged kernel through its wrapper in TPU interpret mode
        against the XLA gather lowering, ragged lengths included."""
        from jax.experimental.pallas import tpu as pltpu
        from paddle_tpu.ops import pallas_kernels as pk
        from paddle_tpu.ops.attention import _paged_reference
        rng = np.random.RandomState(7)
        b, s, r, d, ps = 3, 32, 96, 128, 8
        q = jnp.asarray(rng.randn(b, d).astype("float32"))
        kp = jnp.asarray(rng.randn(r, d).astype("float32"))
        vp = jnp.asarray(rng.randn(r, d).astype("float32"))
        pages = rng.permutation(r // ps)[:b * (s // ps)].reshape(b, -1)
        idx = (pages[:, :, None] * ps + np.arange(ps)).reshape(b, s)
        lens = np.array([5, 32, 17], "int32")
        with pltpu.force_tpu_interpret_mode():
            got = pk.paged_flash_attention_tpu(
                q, kp, vp, jnp.asarray(idx.astype("int32")),
                jnp.asarray(lens), 0.25, page_size=page_size)
        valid = (np.arange(s)[None] < lens[:, None]).astype("float32")
        want = _paged_reference(q, kp, vp,
                                jnp.asarray(idx.reshape(-1).astype("int32")),
                                jnp.asarray(valid), 0.25, 1e30)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_paged_kernel_mosaic_preflight(self):
        """The paged flash kernel compiles through Mosaic offline
        (lane-aligned head dim, scalar-prefetched page table)."""
        import functools
        from paddle_tpu.ops import pallas_kernels as pk
        from paddle_tpu.ops.pallas_preflight import assert_mosaic_lowerable
        q = jnp.zeros((4, 128), jnp.float32)
        pool = jnp.zeros((64, 128), jnp.float32)
        idx = jnp.zeros((4, 16), jnp.int32)
        lengths = jnp.ones((4, 1), jnp.int32)
        assert_mosaic_lowerable(
            functools.partial(pk.paged_flash_attention_tpu, scale=0.25,
                              page_size=4), q, pool, pool, idx, lengths)
