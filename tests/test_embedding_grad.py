"""The gradient of an embedding lookup (PR 38): ``lookup_table_v2`` /
``lookup_table`` add the incoming rows into the dense table's gradient with
``pallas_kernels.embedding_grad_tpu`` where a kernel runs directly
(``manipulation.embedding_grad_lowering``), XLA's scatter elsewhere.  The
kernel runs here in the TPU interpreter against ``jax.vjp`` of ``jnp.take``,
which is what the XLA path is."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import manipulation, pallas_kernels as pk
from paddle_tpu.ops.registry import LoweringContext


def _take_grad(ids, g, vocab):
    w = jnp.zeros((vocab, g.shape[-1]), jnp.float32)
    return jax.vjp(lambda w: jnp.take(w, ids, axis=0), w)[1](g)[0]


def _ids(case, n, vocab, rng):
    if case == "none_repeated":
        return rng.permutation(vocab)[:n]
    if case == "all_equal":
        return np.full(n, vocab // 3)
    if case == "alternating":
        return np.tile([1, vocab - 2], n // 2)
    if case == "ends":                  # the first and the last row, often
        return rng.choice([0, vocab - 1, 5], n)
    if case == "out_of_range":          # negatives count from the end once
        return rng.integers(-2 * vocab, 2 * vocab, n)
    return rng.integers(0, vocab, n)


# (case, tokens, vocab): 300 and 130 rows are not whole 128-row tiles, 200
# and 130 tokens not whole 128-token blocks; 2 rows are one short tile
CASES = [("none_repeated", 200, 300), ("all_equal", 200, 300),
         ("alternating", 256, 300), ("ends", 130, 300),
         ("out_of_range", 130, 130), ("uniform", 384, 1000),
         ("uniform", 300, 2)]


@pytest.mark.parametrize("case, n, vocab", CASES)
def test_kernel_adds_what_the_vjp_of_take_adds(case, n, vocab):
    rng = np.random.default_rng(38)
    ids = jnp.asarray(_ids(case, n, vocab, rng), jnp.int32)
    g = jnp.asarray(rng.standard_normal((n, 128)), jnp.float32)
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(pk.embedding_grad_tpu(ids, g, vocab))
    want = np.asarray(_take_grad(ids, g, vocab))
    assert got.shape == want.shape and got.dtype == np.float32
    if case == "none_repeated":
        # a row without duplicates is its own bits: the three bfloat16
        # terms add back to the float32 row exactly
        np.testing.assert_array_equal(got, want)
    else:
        # duplicates add in float32 in an order of their own
        np.testing.assert_allclose(got, want, rtol=1e-6,
                                   atol=2e-6 * np.abs(want).max())
    np.testing.assert_array_equal(got == 0, want == 0)


class _OnChip(LoweringContext):
    def pallas_ok(self):
        return not self.partitioned


@pytest.mark.parametrize("ctx, shape, want", [
    (LoweringContext(), (300, 128), "xla"),        # the CPU
    (_OnChip(), (300, 128), "kernel"),
    (_OnChip(), (2, 768), "kernel"),               # fewer rows than a tile
    (_OnChip(), (300, 100), "xla"),                # not whole lanes
])
def test_the_rule(ctx, shape, want):
    w = jax.ShapeDtypeStruct(shape, jnp.float32)
    assert manipulation.embedding_grad_lowering(ctx, w) == want
    partitioned = _OnChip()
    partitioned.partitioned = True
    assert manipulation.embedding_grad_lowering(partitioned, w) == "xla"
    assert manipulation.embedding_grad_lowering(
        _OnChip(), jax.ShapeDtypeStruct(shape, jnp.bfloat16)) == "xla"


@pytest.mark.parametrize("op_type", ["lookup_table_v2", "lookup_table"])
def test_both_lookups_take_the_kernel_on_chip(op_type):
    """The op lowerings under ``jax.vjp``, as ``generic_grad`` applies them:
    ``padding_idx``'s rows add nothing and the rest equal XLA's."""
    from paddle_tpu.ops.registry import get_op
    vocab, pad = 260, 3
    rng = np.random.default_rng(2)
    ids = rng.integers(0, vocab, (2, 24))
    ids[0, :4] = [0, vocab - 1, pad, pad]
    ids = jnp.asarray(ids[..., None] if op_type == "lookup_table" else ids,
                      jnp.int32)
    table = jnp.asarray(rng.standard_normal((vocab, 128)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((2, 24, 128)), jnp.float32)

    def grad(ctx):
        fn = get_op(op_type).fn
        out, vjp = jax.vjp(lambda w: fn({"W": [w], "Ids": [ids]},
                                        {"padding_idx": pad}, ctx)["Out"][0],
                           table)
        return out, vjp(g)[0]

    out, want = grad(LoweringContext())
    with pltpu.force_tpu_interpret_mode():
        out_k, got = jax.jit(lambda: grad(_OnChip()))()
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(got)[pad].any() and np.asarray(got)[0].any()


@pytest.mark.parametrize("ctx, lowering", [(LoweringContext(), "xla"),
                                           (_OnChip(), "kernel")])
def test_the_counter_counts_gradients_not_lookups(ctx, lowering):
    """``embedding.grad_lowering.*`` goes up where a gradient is formed: a
    lookup lowered with no gradient (inference) counts nothing, one whose
    vjp is applied counts once."""
    from paddle_tpu.fluid import trace
    from paddle_tpu.ops.registry import get_op
    fn = get_op("lookup_table_v2").fn
    ids = jnp.asarray([[1, 5, 5, 0]], jnp.int32)
    table = jnp.ones((260, 128), jnp.float32)

    def lookup(w):
        return fn({"W": [w], "Ids": [ids]}, {}, ctx)["Out"][0]

    counter = trace.metrics().counter(f"embedding.grad_lowering.{lowering}")
    before = counter.value
    jax.jit(lookup).lower(table)
    assert counter.value == before
    with pltpu.force_tpu_interpret_mode():
        jax.jit(lambda w: jax.vjp(lookup, w)[1](
            jnp.ones((1, 4, 128)))[0]).lower(table)
    assert counter.value == before + 1


def test_the_programs_backward_takes_the_kernel_where_asked(monkeypatch):
    """A Program with an embedding (``padding_idx`` set, ids at both ends of
    the table) through ``append_backward`` and the Executor: on the CPU the
    gradient is XLA's; where the context says a kernel runs directly it is
    the kernel's, equal to it, and ``embedding.grad_lowering.*`` counts
    each."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers as L, trace
    from paddle_tpu.fluid.backward import append_backward
    from paddle_tpu.fluid.core import Scope, scope_guard

    vocab, width, pad = 260, 128, 3
    rng = np.random.default_rng(1)
    ids = rng.integers(0, vocab, (2, 24))
    ids[0, :4] = [0, vocab - 1, pad, pad]
    scale = rng.standard_normal((2, 24, width)).astype(np.float32)

    def run():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("ids", list(ids.shape), dtype="int64")
            s = fluid.data("scale", list(scale.shape), dtype="float32")
            emb = L.embedding(x, [vocab, width], padding_idx=pad,
                              param_attr="table")
            append_backward(L.reduce_sum(L.elementwise_mul(emb, s)))
        with scope_guard(Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            grad, = exe.run(main, feed={"ids": ids, "scale": scale},
                            fetch_list=["table@GRAD"])
            exe.close()
        return np.asarray(grad)

    def count(name):
        return trace.metrics().counter(
            f"embedding.grad_lowering.{name}").value

    before = count("xla")
    xla = run()
    assert count("xla") > before
    flat, rows = ids.reshape(-1), scale.reshape(-1, width).copy()
    rows[flat == pad] = 0
    want = np.zeros((vocab, width), np.float32)
    np.add.at(want, flat, rows)
    np.testing.assert_allclose(xla, want, rtol=1e-5, atol=1e-5)

    calls = []
    real = pk.embedding_grad_tpu

    def spy(*args):
        calls.append(args[2])
        return real(*args)

    monkeypatch.setattr(LoweringContext, "pallas_ok",
                        lambda self: not self.partitioned)
    monkeypatch.setattr(pk, "embedding_grad_tpu", spy)
    before = count("kernel")
    with pltpu.force_tpu_interpret_mode():
        kernel = run()
    assert count("kernel") > before and calls == [vocab]
    np.testing.assert_allclose(kernel, xla, rtol=1e-6, atol=1e-6)
    assert not kernel[pad].any()
