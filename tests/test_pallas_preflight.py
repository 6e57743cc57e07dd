"""Mosaic compile pre-flight (ops/pallas_preflight.py): every pallas
kernel in the repo must compile for the TPU — checked by an AOT compile
for a v5e topology on the CPU host, so a kernel that traces and interprets
fine but dies in Mosaic is caught by the suite, not by the chip.

The rejection tests reconstruct two such failures: a dropout-gelu kernel
written with `lax.erf` (no lowering rule) and a bf16 comparison (v5e's
vector unit has none) must be refused, while the shipped kernels pass."""
import functools

import numpy as np
import pytest
pytestmark = pytest.mark.slow


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops.pallas_preflight import (MosaicLoweringError,
                                             assert_mosaic_lowerable)


def _x(shape=(8, 256), seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype("float32"))


KEY = jax.random.PRNGKey(0)


class TestRejection:
    def test_erf_kernel_rejected(self):
        # round-3's failing kernel shape: gelu-via-lax.erf inside pallas
        def bad_kernel(x_ref, o_ref):
            x = x_ref[...]
            o_ref[...] = 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))

        def run(x):
            return pl.pallas_call(
                bad_kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

        with pytest.raises(MosaicLoweringError, match="erf"):
            assert_mosaic_lowerable(run, _x())

    def test_bf16_compare_rejected(self):
        """Traces and has a lowering rule, but Mosaic refuses it for
        v5e — only a real compile sees this one."""
        def bad_kernel(x_ref, o_ref):
            x = x_ref[...]
            o_ref[...] = (x > 0).astype(x.dtype)

        def run(x):
            return pl.pallas_call(
                bad_kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

        with pytest.raises(MosaicLoweringError, match="comparison"):
            assert_mosaic_lowerable(run, _x().astype(jnp.bfloat16))

    def test_no_kernel_rejected_by_default(self):
        with pytest.raises(MosaicLoweringError, match="no pallas_call"):
            assert_mosaic_lowerable(lambda x: x + 1, _x())

    def test_plain_fn_ok_when_kernels_not_required(self):
        assert_mosaic_lowerable(lambda x: jnp.tanh(x) + 1, _x(),
                                require_kernels=False)


class TestRepoKernels:
    """Forward AND backward of every shipped pallas entry point.  The PRNG
    key rides in as an argument, as it does under the executor's jit."""

    def test_fused_dropout_fwd_bwd(self):
        f = lambda x, k: pk.fused_dropout_tpu(x, k, 0.3, True)[0]
        assert_mosaic_lowerable(f, _x(), KEY)
        assert_mosaic_lowerable(
            jax.grad(lambda x, k: f(x, k).sum()), _x(), KEY)

    def test_fused_dropout_mask_kernel(self):
        assert_mosaic_lowerable(
            lambda x, k: pk.fused_dropout_tpu(x, k, 0.3, True)[1](),
            _x(), KEY)

    def test_fused_dropout_add_fwd_bwd(self):
        def f(x, r, k):
            return pk.fused_dropout_add_tpu(x, r, k, 0.3, True)
        assert_mosaic_lowerable(f, _x(), _x(seed=1), KEY)
        assert_mosaic_lowerable(
            jax.grad(lambda x, r, k: f(x, r, k).sum(), argnums=(0, 1)),
            _x(), _x(seed=1), KEY)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("act", ["gelu", "relu"])
    def test_fused_act_dropout_fwd_bwd(self, act, dtype):
        def f(x, k):
            return pk.fused_act_dropout_tpu(x, k, 0.3, True, act)
        x = _x().astype(dtype)
        assert_mosaic_lowerable(f, x, KEY)
        assert_mosaic_lowerable(
            jax.grad(lambda x, k: f(x, k).astype(jnp.float32).sum()),
            x, KEY)

    def test_flash_attention(self):
        q = _x((1, 2, 256, 64))
        k = _x((1, 2, 256, 64), 1)
        v = _x((1, 2, 256, 64), 2)
        assert_mosaic_lowerable(
            lambda q, k, v: pk.flash_attention_tpu(q, k, v), q, k, v)

    def test_flash_attention_bwd(self):
        q = _x((1, 2, 256, 64))
        k = _x((1, 2, 256, 64), 1)
        v = _x((1, 2, 256, 64), 2)
        g = jax.grad(lambda q, k, v: pk.flash_attention_tpu(q, k, v).sum(),
                     argnums=(0, 1, 2))
        assert_mosaic_lowerable(g, q, k, v)

    @pytest.mark.parametrize("shape", [(32, 12, 512, 64),
                                       (128, 12, 128, 64)])
    def test_fused_attention_fwd_bwd(self, shape):
        """BERT-base's widths in bf16, the padding bias as its
        [B, 1, 1, S] row, dropout 0.1 on the probabilities."""
        b, _, s, _ = shape
        q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        bias = jax.ShapeDtypeStruct((b, 1, 1, s), jnp.float32)

        def f(q, k, v, bias, key):
            return pk.fused_attention_tpu(q, k, v, bias, dropout_rate=0.1,
                                          dropout_key=key)
        assert_mosaic_lowerable(f, q, q, q, bias, KEY)
        assert_mosaic_lowerable(
            jax.grad(lambda *a: f(*a).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2)), q, q, q, bias, KEY)

    def test_fused_attention_mask_kernel(self):
        assert_mosaic_lowerable(
            lambda k: pk.fused_attention_keep_mask((8, 12, 512, 64), 512,
                                                   0.1, k), KEY)


    def test_indexer_loss_passes(self):
        """The indexer's loss at ``keye_vl2_train_seq16384``'s widths (32 :
        4 heads of 128, 16 index heads of 64, bfloat16), two super blocks
        of 2048 queries: the heads' mean probabilities, the row statistics
        with the KL, and the gradients of QI, KI and W with dKI resident:
        three kernels a super block."""
        seq, sds, bf16 = 4096, jax.ShapeDtypeStruct, jnp.bfloat16
        args = (sds((16, seq, 64), bf16), sds((seq, 64), bf16),
                sds((seq, 16), jnp.float32), sds((4, 8, seq, 128), bf16),
                sds((4, seq, 128), bf16), sds((4, 8, seq), jnp.float32),
                sds((seq, seq // 8), jnp.uint8))
        from paddle_tpu.ops.pallas_preflight import (compile_for_tpu,
                                                     mosaic_call_count)
        compiled = compile_for_tpu(
            lambda *a: pk.index_kl_tpu(*a, 128 ** -0.5, 2048, True), *args)
        assert mosaic_call_count(compiled) == 6

    @pytest.mark.parametrize("seq", [4096, 4096 + 40])
    def test_selective_scan_fwd_bwd(self, seq):
        """The scan's two kernels at ``phi4_flash_train_seq4096``'s shape
        (5120 channels of 16 states), and with a ragged last chunk."""
        f32 = jnp.float32
        sds = jax.ShapeDtypeStruct
        args = (sds((1, seq, 5120), f32), sds((1, seq, 5120), f32),
                sds((5120, 16), f32), sds((1, seq, 16), f32),
                sds((1, seq, 16), f32), sds((5120,), f32))
        assert pk.selective_scan_supported(args[0], args[2])
        from paddle_tpu.ops.pallas_preflight import (compile_for_tpu,
                                                     mosaic_call_count)
        compiled = compile_for_tpu(
            lambda *a: jax.value_and_grad(
                lambda *b: jnp.sum(pk.selective_scan_tpu(*b)[0]),
                argnums=tuple(range(6)))(*a), *args)
        assert mosaic_call_count(compiled) == 2

    @pytest.mark.parametrize("n, vocab, width", [
        (4096, 25008, 2560), (16384, 30522, 768), (16384, 2, 768)])
    def test_embedding_grad(self, n, vocab, width):
        """The embedding's gradient at ``phi4_flash_train_seq4096``'s table
        (a ragged last tile) and at BERT's word and token-type tables."""
        sds = jax.ShapeDtypeStruct
        args = (sds((n,), jnp.int32), sds((n, width), jnp.float32))
        from paddle_tpu.ops.pallas_preflight import (compile_for_tpu,
                                                     mosaic_call_count)
        compiled = compile_for_tpu(
            lambda *a: pk.embedding_grad_tpu(*a, vocab), *args)
        assert mosaic_call_count(compiled) == 1

    @pytest.mark.parametrize("window", [512, 0])
    def test_splash_takes_a_64_wide_score_head(self, window):
        """Differential attention's head: scores over 64, values of 128,
        40 : 20 heads, 4096 tokens; forward, dq and dk/dv."""
        bf16 = jnp.bfloat16
        sds = jax.ShapeDtypeStruct
        args = (sds((1, 40, 4096, 64), bf16), sds((1, 20, 4096, 64), bf16),
                sds((1, 20, 4096, 128), bf16))
        assert pk.splash_attention_supported(*args, None)
        from paddle_tpu.ops.pallas_preflight import (compile_for_tpu,
                                                     mosaic_call_count)
        compiled = compile_for_tpu(
            lambda *a: jax.value_and_grad(
                lambda *b: jnp.sum(pk.splash_attention_tpu(
                    *b, scale=0.125, window=window).astype(jnp.float32)),
                argnums=(0, 1, 2))(*a), *args)
        assert mosaic_call_count(compiled) == 3

    @pytest.mark.parametrize("op, tokens, z_dtype", [
        ("mix", 4096, None), ("merge", 4096, "float32"),
        ("mix", 4096 + 40, None), ("merge", 4096 + 40, "bfloat16")])
    def test_hyper_connection_mixers_fwd_bwd(self, op, tokens, z_dtype):
        """Both mixer ops, forward and backward (five kernels), at
        ``xing4_train_seq4096``'s shape: 4096 tokens of four float32 streams
        of 3584, ``Phi`` [14336, 24], the branch's output widened by XLA
        before the merge sees it; and with a ragged last tile, the branch's
        output in bfloat16."""
        f32 = jnp.float32
        n, d = 4, 3584
        x = jax.ShapeDtypeStruct((tokens, n * d), f32)
        assert pk.hyper_connection_supported(x, n)
        if op == "mix":
            def f(x, phi, alpha, b):
                return pk.hyper_connection_mix_tpu(
                    x, phi, alpha, b, n, 1e-6, 20, 1e-6, (-30.0, 30.0))[:3]
            args = (x, jax.ShapeDtypeStruct((n * d, n * n + 2 * n), f32),
                    jax.ShapeDtypeStruct((3,), f32),
                    jax.ShapeDtypeStruct((n * n + 2 * n,), f32))
        else:
            f = pk.hyper_connection_merge_tpu
            args = (x, jax.ShapeDtypeStruct((tokens, d), jnp.dtype(z_dtype)),
                    jax.ShapeDtypeStruct((tokens, n), f32),
                    jax.ShapeDtypeStruct((tokens, n * n), f32))
        assert_mosaic_lowerable(f, *args)

        def grads(*a):
            out, vjp = jax.vjp(f, *a)
            return vjp(out)
        assert_mosaic_lowerable(grads, *args)
