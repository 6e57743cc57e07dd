"""The selective scan of a state-space layer (ops/selective_scan.py): both
lowerings (the ``jnp`` path over several chunk sizes, the Pallas kernels in
the interpreter) against a per-token loop, the output and all six gradients,
sequences that are not whole chunks, bfloat16 operands with a float32 state;
the causal convolution before it; the Program ops, their layers and gauges;
the AMP lists."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops import selective_scan as ss
from paddle_tpu.ops.registry import LoweringContext, get_op

SLOTS = ("X", "Dt", "A", "B", "C", "D")


def _operands(bsz, seq, di, n, seed=0, dtype="float32"):
    r = np.random.RandomState(seed)
    x = r.randn(bsz, seq, di)
    dt = np.log1p(np.exp(r.randn(bsz, seq, di) - 2.0))
    a = -np.tile(np.arange(1, n + 1, dtype="float64"), (di, 1)) \
        * r.uniform(0.5, 1.5, (di, 1))
    b, c = r.randn(bsz, seq, n), r.randn(bsz, seq, n)
    d = r.randn(di)
    return tuple(jnp.asarray(v, dtype if v.ndim == 3 else "float32")
                 for v in (x, dt, a, b, c, d))


def _loop(x, dt, a, b, c, d):
    """The recurrence a token at a time, the state [B, Di, N] float32."""
    x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))

    def step(s, tok):
        xt, dtt, bt, ct = tok
        s = jnp.exp(dtt[:, :, None] * a) * s \
            + (dtt * xt)[:, :, None] * bt[:, None, :]
        return s, jnp.einsum("bdn,bn->bd", s, ct) + d * xt
    s0 = jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(jnp.moveaxis(v, 1, 0)
                                        for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def _value_and_grads(scan, args, weight):
    return jax.value_and_grad(
        lambda *a: jnp.sum(scan(*a).astype(jnp.float32) * weight),
        argnums=range(6))(*args)


def _rel(got, want):
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


@pytest.mark.parametrize("seq, chunk", [(48, 16), (48, 48), (50, 16),
                                        (37, 64), (64, 7)])
def test_jnp_path_equals_the_loop(seq, chunk):
    args = _operands(2, seq, 8, 4, seed=seq)
    weight = jnp.asarray(np.random.RandomState(1).randn(2, seq, 8),
                         jnp.float32)
    want, want_grads = _value_and_grads(_loop, args, weight)
    got, got_grads = _value_and_grads(
        lambda *a: ss.selective_scan_xla(*a, chunk=chunk)[0], args, weight)
    assert _rel(got, want) < 1e-5
    for slot, g, w in zip(SLOTS, got_grads, want_grads):
        assert _rel(g, w) < 2e-5, slot


def test_jnp_path_keeps_the_state_after_each_chunk_and_nothing_longer():
    args = _operands(1, 40, 8, 4)
    y, ends = ss.selective_scan_xla(*args, chunk=16)
    assert y.shape == (1, 40, 8) and ends.shape == (1, 3, 4, 8)
    # the state after the first chunk is the loop's after 16 tokens
    x, dt, a, b, c, d = args
    s = np.zeros((8, 4))
    for t in range(16):
        s = np.exp(np.asarray(dt[0, t])[:, None] * np.asarray(a)) * s \
            + np.asarray(dt[0, t] * x[0, t])[:, None] * np.asarray(b[0, t])
    np.testing.assert_allclose(ends[0, 0].T, s, rtol=1e-5, atol=1e-6)
    # no [S, Di, N] array in what autodiff keeps for backward
    _, vjp = jax.vjp(lambda *a: ss.selective_scan_xla(*a, chunk=16)[0],
                     *args)
    kept = max(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(vjp))
    assert kept < 40 * 8 * 4


@pytest.mark.parametrize("seq", [256, 300, 512])
def test_kernels_in_interpret_mode_equal_the_loop(seq):
    """Forward and all six gradients of the Pallas kernels (whole chunks, a
    ragged last chunk, two chunks with the state carried between them)."""
    args = _operands(2, seq, 256, 16, seed=seq)
    weight = jnp.asarray(np.random.RandomState(2).randn(2, seq, 256),
                         jnp.float32)
    assert pk.selective_scan_supported(args[0], args[2])
    want, want_grads = _value_and_grads(_loop, args, weight)
    with pltpu.force_tpu_interpret_mode():
        got, got_grads = _value_and_grads(
            lambda *a: pk.selective_scan_tpu(*a)[0], args, weight)
        _, ends = pk.selective_scan_tpu(*args)
    assert _rel(got, want) < 1e-5
    for slot, g, w in zip(SLOTS, got_grads, want_grads):
        assert _rel(g, w) < 2e-5, slot
    _, want_ends = ss.selective_scan_xla(*args)
    np.testing.assert_allclose(ends, want_ends, rtol=1e-4, atol=1e-5)


def test_bfloat16_operands_keep_a_float32_state():
    """bfloat16 x, dt, B, C: both lowerings compute what the loop computes
    from the same rounded operands in float32, and the kept states are
    float32; a bfloat16 STATE would read two orders worse."""
    args = _operands(1, 256, 128, 8, dtype="bfloat16")
    want = _loop(*args)
    y, ends = ss.selective_scan_xla(*args)
    assert ends.dtype == jnp.float32 and _rel(y, want) < 1e-5
    with pltpu.force_tpu_interpret_mode():
        y, ends = pk.selective_scan_tpu(*args)
    assert ends.dtype == jnp.float32 and _rel(y, want) < 1e-5

    def rounded_state(x, dt, a, b, c, d):
        def step(s, tok):
            xt, dtt, bt, ct = (v.astype(jnp.float32) for v in tok)
            s = (jnp.exp(dtt[:, None] * a) * s + (dtt * xt)[:, None] * bt
                 ).astype(jnp.bfloat16).astype(jnp.float32)
            return s, s @ ct + d * xt
        return jax.lax.scan(step, jnp.zeros((128, 8)),
                            (x[0], dt[0], b[0], c[0]))[1][None]
    assert _rel(rounded_state(*args), want) > 1e-3


@pytest.mark.parametrize("shape, n, want", [
    ((1, 4096, 5120), 16, True),        # the published widths
    ((2, 256, 128), 8, True),
    ((1, 4096, 5100), 16, False),       # channels not whole lane groups
    ((1, 128, 5120), 16, False),        # under one chunk
    ((1, 4096, 5120), 12, False),       # states not whole sublane tiles
    ((1, 4096, 5120), 64, False),       # a state too large for registers
])
def test_the_kernels_rule(shape, n, want):
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    a = jax.ShapeDtypeStruct((shape[2], n), jnp.float32)
    assert pk.selective_scan_supported(x, a) is want


def test_causal_conv_sees_the_past_alone():
    r = np.random.RandomState(3)
    x = r.randn(2, 10, 6).astype("float32")
    w, bias = r.randn(4, 6).astype("float32"), r.randn(6).astype("float32")
    out = np.asarray(get_op("causal_conv1d").fn(
        {"X": [jnp.asarray(x)], "W": [jnp.asarray(w)],
         "Bias": [jnp.asarray(bias)]}, {}, LoweringContext())["Out"][0])
    want = np.zeros_like(x)
    for t in range(10):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += w[k] * x[:, t - 3 + k]
    np.testing.assert_allclose(out, want + bias, rtol=1e-5, atol=1e-6)
    # a later token moves no earlier output
    x2 = x.copy()
    x2[:, 7] += 1.0
    out2 = np.asarray(get_op("causal_conv1d").fn(
        {"X": [jnp.asarray(x2)], "W": [jnp.asarray(w)],
         "Bias": [jnp.asarray(bias)]}, {}, LoweringContext())["Out"][0])
    np.testing.assert_array_equal(out2[:, :7], out[:, :7])
    assert np.abs(out2[:, 7] - out[:, 7]).max() > 0


def test_the_op_counts_its_lowering_and_publishes_its_gauges():
    """Through the Executor: the layer's output is the loop's, the gradient
    reaches all six operands, ``ssm.lowering.xla`` counts the CPU's pick
    and the gauges leave the device when a runner drains."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers as L, trace
    from paddle_tpu.fluid.async_pipeline import AsyncStepRunner
    from paddle_tpu.fluid.backward import append_backward
    from paddle_tpu.fluid.core import Scope, scope_guard

    args = _operands(2, 24, 8, 4, seed=5)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feeds = [fluid.data(n, list(v.shape), dtype="float32")
                 for n, v in zip("x dt a b c d".split(), args)]
        for f in feeds:
            f.stop_gradient = False
        y = L.selective_scan(*feeds, gauges="layer_0", name="layer_0.scan")
        loss = L.reduce_sum(L.square(y))
        grads = append_backward(loss, parameter_list=[f.name for f in feeds])
    assert y.name.startswith("layer_0.scan")
    before = trace.metrics().counter("ssm.lowering.xla").value
    feed = {n: np.asarray(v) for n, v in zip("x dt a b c d".split(), args)}
    want, want_grads = jax.value_and_grad(
        lambda *a: jnp.sum(jnp.square(_loop(*a))), argnums=range(6))(*args)
    with scope_guard(Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        got = exe.run(main, feed=feed,
                      fetch_list=[loss.name] + [g.name for _, g in grads])
        assert _rel(got[0], want) < 1e-5
        for slot, g, w in zip(SLOTS, got[1:], want_grads):
            assert _rel(g, w) < 2e-5, slot
        runner = AsyncStepRunner(exe, main, [loss])
        runner.submit(feed)
        runner.drain()
    assert trace.metrics().counter("ssm.lowering.xla").value > before
    assert trace.gauge_value("ssm.layer_0.state_abs_max", -1.0) > 0
    np.testing.assert_allclose(
        trace.gauge_value("ssm.layer_0.dt_mean", -1.0),
        float(jnp.mean(args[1])), rtol=1e-5)


def test_amp_keeps_the_scan_float32_and_the_conv_bfloat16():
    from paddle_tpu.amp import lists
    assert lists.classify("selective_scan") == "black"
    assert lists.classify("softplus") == "black"
    assert lists.classify("causal_conv1d") == "white"
    assert lists.classify("swiglu") == "gray"
    assert lists.unclassified_family_ops() == []
