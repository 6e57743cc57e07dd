"""The forward runs once (fluid/backward.py, fluid/executor.py): when
``run_block_ops`` lowers a forward op whose ``generic_grad`` is in the same
op list it does so under ``jax.vjp`` and the grad op applies that vjp; every
other route to a ``generic_grad`` traces the forward again.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import backward, device_stats as ds, trace
from paddle_tpu.fluid import layers as L
from paddle_tpu.fluid.core import Scope, scope_guard
from paddle_tpu.fluid.executor import run_block_ops
from paddle_tpu.fluid.framework import reset_unique_name
from paddle_tpu.ops import registry
from paddle_tpu.ops.registry import LoweringContext


def _counts():
    return {n: trace.metrics().counter("backward.vjp_" + n).value
            for n in ("kept", "retraced")}


def _moved(before):
    return {n: v - before[n] for n, v in _counts().items()}


# ---------------------------------------------------------------------------
# (a) the kept vjp gives the re-traced gradients, bit for bit
# ---------------------------------------------------------------------------

def _data(name, shape, dtype="float32"):
    v = fluid.data(name, shape, dtype=dtype)
    v.stop_gradient = False
    return v


def _mul():
    x = _data("x", [-1, 6])
    return L.fc(x, 5, bias_attr=False), {"x": (4, 6)}


def _layer_norm():
    x = _data("x", [-1, 6])
    return L.layer_norm(x, begin_norm_axis=1), {"x": (4, 6)}


def _dropout():
    x = _data("x", [-1, 6])
    return L.dropout(x, 0.4, seed=11,
                     dropout_implementation="upscale_in_train"), {"x": (4, 6)}


def _softmax_xent():
    x = _data("x", [-1, 5])
    label = fluid.data("label", [-1, 1], dtype="int64")
    return L.softmax_with_cross_entropy(x, label), {"x": (4, 5),
                                                    "label": (4, 1)}


def _attention():
    q, k, v = (_data(n, [-1, 2, 8, 4]) for n in "qkv")
    return L.fused_multihead_attention(q, k, v), {n: (2, 2, 8, 4)
                                                  for n in "qkv"}


CASES = {"mul": _mul, "layer_norm": _layer_norm, "dropout": _dropout,
         # custom_grad: stays on the path that calls the forward again
         "softmax_with_cross_entropy": _softmax_xent,
         "fused_multihead_attention": _attention}


def _gradients(build, pair):
    """Loss and every input/parameter gradient of mean(op(..)^2) through
    the Executor, with the pairing as the code finds it or with none."""
    reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        out, shapes = build()
        loss = L.mean(L.elementwise_mul(out, out))
        backward.append_backward(loss)
    block = main.global_block()
    grads = sorted(n for op in block.ops if op.type == "generic_grad"
                   for n in op.output_arg_names)
    rng = np.random.RandomState(3)
    feed = {n: (rng.randint(0, s[-1] + 4, s).astype("int64") if n == "label"
                else rng.randn(*s).astype("float32"))
            for n, s in shapes.items()}
    before = _counts()
    with pytest.MonkeyPatch.context() as mp:
        if not pair:
            mp.setattr(backward, "pair_grads", lambda ops: {})
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            vals = exe.run(main, feed=feed, fetch_list=[loss.name] + grads)
        exe.close()
    return [np.asarray(v) for v in vals], _moved(before), block


@pytest.mark.parametrize("name", sorted(CASES))
def test_kept_vjp_gradients_equal_the_retraced_ones(name):
    build = CASES[name]
    kept_pairs = int(registry.get_op(name).custom_grad is None)
    kept, moved, block = _gradients(build, pair=True)
    retraced, moved_off, _ = _gradients(build, pair=False)
    n_grads = sum(op.type == "generic_grad" for op in block.ops)
    own = sum(op.type == "generic_grad" and op.attrs["fwd_type"] == name
              for op in block.ops)
    assert own == 1
    # every pair but the custom_grad one is kept; none without the pairing
    assert moved == {"kept": n_grads - (1 - kept_pairs),
                     "retraced": 1 - kept_pairs}
    assert moved_off == {"kept": 0, "retraced": n_grads}
    assert len(kept) == len(retraced) >= 2
    for got, want in zip(kept, retraced):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want)
    assert all(np.isfinite(v).all() for v in kept)
    assert any(np.abs(v).sum() > 0 for v in kept[1:])


# ---------------------------------------------------------------------------
# (b) the forward lowering is called once per pair, twice on a fallback
# ---------------------------------------------------------------------------

@pytest.fixture
def counted_op():
    """A differentiable op whose lowering counts its own calls."""
    calls = []

    def lowering(ins, attrs, ctx):
        calls.append(1)
        return {"Out": [ins["X"][0] * attrs["factor"]]}
    registry.register_op("counted_scale_for_test", lowering, custom=True)
    yield calls
    del registry._OP_REGISTRY["counted_scale_for_test"]


def _counted_program(overwrite_input=False):
    reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = _data("x", [-1, 3])
        h = L.scale(x, scale=2.0)
        block = main.global_block()
        out = block.create_var(name="counted.out", dtype="float32",
                               shape=[-1, 3])
        block.append_op("counted_scale_for_test", inputs={"X": [h]},
                        outputs={"Out": [out]}, attrs={"factor": 3.0})
        loss = L.mean(out)
        backward.append_backward(loss)
        if overwrite_input:
            # something writes the forward's input before its grad reads it
            at = [op.type for op in block.ops].index(
                "counted_scale_for_test") + 1
            block._insert_op(at, "scale", inputs={"X": [h]},
                             outputs={"Out": [h]}, attrs={"scale": 1.0})
    return main, startup, loss


def _split_at_backward(block):
    first = next(i for i, op in enumerate(block.ops)
                 if op.attrs.get("op_role") == 1)
    return block.ops[:first], block.ops[first:]


def test_executor_training_program_traces_the_forward_once(counted_op):
    main, startup, loss = _counted_program()
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        del counted_op[:]               # the build's shape inference
        before = _counts()
        _, gx = exe.run(main, feed={"x": np.ones((2, 3), "float32")},
                        fetch_list=[loss.name, "x@GRAD"])
    exe.close()
    assert len(counted_op) == 1
    assert _moved(before) == {"kept": 3, "retraced": 0}
    np.testing.assert_allclose(gx, np.full((2, 3), 1.0), rtol=1e-6)


@pytest.mark.parametrize("route", ["direct_call", "ops_subset",
                                   "input_overwritten"])
def test_fallback_routes_trace_the_forward_again(counted_op, route):
    main, _, _ = _counted_program(route == "input_overwritten")
    block = main.global_block()
    fwd, bwd = _split_at_backward(block)
    env = {"x": jnp.ones((2, 3), jnp.float32)}
    ctx = LoweringContext()
    del counted_op[:]
    before = _counts()
    if route == "direct_call":
        # what dygraph/base.py's tape does: no run_block_ops around it
        run_block_ops(block, env, ctx, ops=fwd)
        g = next(op for op in bwd if op.type == "generic_grad"
                 and op.attrs["fwd_type"] == "counted_scale_for_test")
        ins = {slot: [env[n] if n in env else jnp.full((2, 3), 1 / 6.0)
                      for n in names] for slot, names in g.inputs.items()}
        outs = backward._generic_grad(ins, g.attrs, ctx)
        env[g.outputs["GI_X"][0]] = outs["GI_X"][0]
        want = {"kept": 0, "retraced": 1}
    elif route == "ops_subset":
        # a pipeline stage / recompute segment: the grads without their
        # forwards in the op list
        run_block_ops(block, env, ctx, ops=fwd)
        run_block_ops(block, env, ctx, ops=bwd)
        want = {"kept": 0, "retraced": 3}
    else:
        run_block_ops(block, env, ctx)
        want = {"kept": 2, "retraced": 1}
    assert len(counted_op) == 2
    assert _moved(before) == want
    gname = next(n for op in bwd for n in op.output_arg_names
                 if n.startswith("scale_0.tmp_0@GRAD"))
    np.testing.assert_allclose(env[gname], np.full((2, 3), 0.5), rtol=1e-6)
    assert ctx.kept_vjp is None


def test_pairing_reads_the_program_not_a_name():
    """One grad per forward, matched on type, input names, attrs and the
    folded AMP casts; anything else is left to re-trace."""
    reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = _data("x", [-1, 4])
        loss = L.mean(L.fc(L.fc(x, 4, bias_attr=False), 4, bias_attr=False))
        backward.append_backward(loss)
    ops = main.global_block().ops
    muls = [op for op in ops if op.type == "mul"]
    grads = [op for op in ops if op.type == "generic_grad"
             and op.attrs["fwd_type"] == "mul"]
    pairs = backward.pair_grads(ops)
    assert [pairs[id(f)] for f in muls] == grads[::-1]
    # an attr the grad does not know of, or a folded cast on one side only
    muls[0].attrs["x_num_col_dims"] = 2
    assert id(muls[0]) not in backward.pair_grads(ops)
    muls[0].attrs["x_num_col_dims"] = 1
    muls[1].attrs["__amp_cast__"] = {"X": ["bfloat16"]}
    assert id(muls[1]) not in backward.pair_grads(ops)
    grads[0].attrs["__amp_cast__"] = {"I_X": ["bfloat16"],
                                      "G_Out": ["float32"]}
    assert backward.pair_grads(ops)[id(muls[1])] is grads[0]
    # the grad before its forward, or without it, pairs with nothing
    assert id(muls[0]) not in backward.pair_grads(
        [grads[1], muls[0]])
    assert backward.pair_grads([op for op in ops
                                if op.type == "generic_grad"]) == {}


# ---------------------------------------------------------------------------
# (c) a tiny BERT under amp and the default pipeline
# ---------------------------------------------------------------------------

def _pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, out)
    return out


def test_tiny_bert_step_holds_one_forward_attention_per_layer(monkeypatch):
    from paddle_tpu.models.static_graphs import (bert_demo_feed,
                                                 build_bert_train_program)
    # the lowerings ask jax.default_backend(); the target is the TPU
    monkeypatch.setattr(LoweringContext, "pallas_ok",
                        lambda self: not self.partitioned)
    batch, seq, layers = 2, 128, 2
    reset_unique_name()
    main, startup, loss = build_bert_train_program(
        vocab=64, hidden=128, heads=2, seq=seq, layers=layers, dropout=0.1)
    bs = fluid.BuildStrategy()
    bs.amp = True
    program = fluid.CompiledProgram(main, build_strategy=bs)
    feed = bert_demo_feed(np.random.RandomState(0), batch=batch, seq=seq,
                          vocab=64)
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
        before = _counts()
        jaxpr = jax.make_jaxpr(step.raw_fn)(mut, ro, feed,
                                            jax.random.PRNGKey(0))
    exe.close()
    ops = main.global_block().ops
    grads = [op for op in ops if op.type == "generic_grad"]
    assert sum(op.type == "fused_multihead_attention"
               for op in ops) == layers
    assert sum(g.attrs["fwd_type"] == "fused_multihead_attention"
               for g in grads) == layers
    # the AMP pairs (folded casts on both sides) are kept; the loss's
    # custom_grad is the one generic_grad that calls its forward again
    assert any("__amp_cast__" in g.attrs for g in grads)
    custom = [g for g in grads
              if registry.get_op(g.attrs["fwd_type"]).custom_grad]
    assert [g.attrs["fwd_type"] for g in custom] \
        == ["softmax_with_cross_entropy"]
    assert _moved(before) == {"kept": len(grads) - 1, "retraced": 1}
    # per layer one forward kernel (out, lse) and one backward (dq, dk, dv);
    # one more for the word embedding's gradient (embedding_grad_tpu)
    calls = _pallas_calls(jaxpr.jaxpr, [])
    assert sorted(len(c.outvars) for c in calls) == [1] + [2] * layers \
        + [3] * layers


# ---------------------------------------------------------------------------
# (d) the op_name shapes a kept vjp leaves in the executable
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path, want", [
    # the forward with its residuals, under the forward op's scope
    ("jit(fn)/pd:f:fused_multihead_attention:matmul_1.tmp_0/"
     "jvp(jit(_fused_attention_jit))/pallas_call",
     ("fused_multihead_attention", "forward", "matmul_1.tmp_0")),
    ("jit(fn)/pd:f:fused_multihead_attention:matmul_1.tmp_0/jvp()/"
     "vmap(jit(_threefry_fold_in))/LoweringContext.key_for/xor",
     ("fused_multihead_attention", "forward", "matmul_1.tmp_0")),
    ("jit(fn)/pd:f:mul:fc_0.tmp_0/jvp()/dot_general",
     ("mul", "forward", "fc_0.tmp_0")),
    # the transposed half, under the grad op's scope alone
    ("jit(fn)/pd:b:fused_multihead_attention_grad:transpose2_0.tmp_0.GRAD/"
     "transpose(jvp(jit(_fused_attention_jit)))/pallas_call",
     ("fused_multihead_attention_grad", "backward",
      "transpose2_0.tmp_0.GRAD")),
    ("jit(fn)/pd:b:mul_grad:fc_0.w_0.GRAD/transpose(jvp())/dot_general",
     ("mul_grad", "backward", "fc_0.w_0.GRAD")),
    # a custom_vjp's backward rule names the forward scope inside
    # transpose(..): the function it transposed, not a frame of the path
    ("jit(fn)/pd:b:dropout_grad:fc_3.tmp_1.GRAD/"
     "transpose(pd:f:dropout:dropout_3.tmp_0)/jvp()/pallas_call",
     ("dropout_grad", "backward", "fc_3.tmp_1.GRAD")),
    ("jit(fn)/pd:f:while:out_0/while/body/pd:b:dropout_grad:g/"
     "transpose(pd:f:dropout:o/jvp(jit(body)))/shard_map/pallas_call",
     ("dropout_grad", "backward", "g")),
    # once per chip in a partitioned program
    ("jit(constrained)/pd:f:dropout:dropout_0.tmp_0/jvp(jit(body))/"
     "shard_map/pallas_call", ("dropout", "forward", "dropout_0.tmp_0")),
])
def test_parse_scope_on_kept_vjp_op_names(path, want):
    assert ds.parse_scope(path) == want


def test_kept_vjp_charges_forward_and_backward_to_their_ops():
    """Through the Executor on the CPU: with the scope opened around
    ``jax.vjp`` the forward's instructions carry the forward op's scope and
    the transposed ones the grad op's, never each other's."""
    import re
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        ds._remembered.clear()
        reset_unique_name()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = _data("x", [-1, 6])
            loss = L.mean(L.tanh(L.fc(x, 5, bias_attr=False)))
            backward.append_backward(loss)
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(main, feed={"x": np.ones((4, 6), "float32")},
                    fetch_list=[loss.name, "x@GRAD"])
        entry = list(ds._remembered.values())[-1]
        text = ds._aot_compile(entry["jitted"], entry["examples"]).as_text()
        exe.close()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
        ds._remembered.clear()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    fwd = [n for n in names if "/jvp(" in n and "transpose(" not in n]
    bwd = [n for n in names if "transpose(jvp(" in n]
    assert fwd and bwd
    assert {ds.parse_scope(n)[1] for n in fwd} == {"forward"}
    assert {ds.parse_scope(n)[1] for n in bwd} == {"backward"}
    assert {ds.parse_scope(n)[0] for n in bwd} <= {"mul_grad", "tanh_grad",
                                                   "mean_grad"}
    assert not any("pd:f:" in n for n in bwd)
    assert not any("pd:b:" in n for n in fwd)
