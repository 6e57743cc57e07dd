"""Fused dropout epilogues (ops/pallas_kernels.py + ops/nn_ops.py):
dropout+residual-add and act+dropout as single ops.

On TPU these are single pallas kernels with mask regeneration in
backward; on CPU the ops take the bernoulli fallback with identical
semantics — these tests pin the op contract (eval-mode exactness,
train-mode statistics, gradient structure) on any backend, and the
TPU-only class adds the pallas/jnp cross-check when a chip is present.
Fusion motivation: round-3 sweep showed ~13 MFU points lost at the
dropout kernel boundaries."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.dygraph import base as dybase
from paddle_tpu.dygraph.base import to_variable
import paddle_tpu.fluid.layers as L


@pytest.fixture
def dygraph():
    dybase.enable_dygraph()
    yield
    dybase.disable_dygraph()


def rand(shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype("float32")


class TestFusedDropoutAdd:
    def test_eval_mode_is_exact_add(self, dygraph):
        x, r = rand((8, 256)), rand((8, 256), 1)
        out = L.fused_dropout_add(to_variable(x), to_variable(r), 0.3,
                                  is_test=True)
        np.testing.assert_allclose(out.numpy(), x + r, rtol=1e-6)

    def test_zero_rate_is_exact_add(self, dygraph):
        x, r = rand((8, 256)), rand((8, 256), 1)
        out = L.fused_dropout_add(to_variable(x), to_variable(r), 0.0)
        np.testing.assert_allclose(out.numpy(), x + r, rtol=1e-6)

    def test_train_mode_structure(self, dygraph):
        """out - r is elementwise either 0 or x/(1-p): the dropped set is
        a genuine mask and survivors are upscaled."""
        p = 0.4
        x, r = rand((64, 256), 2) + 3.0, rand((64, 256), 3)
        out = L.fused_dropout_add(to_variable(x), to_variable(r), p)
        d = out.numpy() - r
        kept = np.abs(d) > 1e-6
        np.testing.assert_allclose(d[kept], (x / (1 - p))[kept], rtol=1e-4)
        frac = 1.0 - kept.mean()
        assert abs(frac - p) < 0.05, frac

    def test_gradients_match_mask(self, dygraph):
        """d/dresidual == 1 exactly; d/dx == mask/(1-p), consistent with
        the forward's kept set (the regenerated-mask contract)."""
        p = 0.3
        x, r = to_variable(rand((32, 128), 4) + 2.0), \
            to_variable(rand((32, 128), 5))
        x.stop_gradient = False
        r.stop_gradient = False
        out = L.fused_dropout_add(x, r, p)
        kept = np.abs(out.numpy() - r.numpy()) > 1e-6
        loss = L.reduce_sum(out)
        loss.backward()
        np.testing.assert_allclose(r.gradient(), np.ones_like(r.numpy()),
                                   rtol=1e-6)
        gx = x.gradient()
        np.testing.assert_allclose(gx[kept], 1.0 / (1 - p), rtol=1e-4)
        np.testing.assert_allclose(gx[~kept], 0.0, atol=1e-7)


class TestFusedActDropout:
    def test_eval_mode_is_exact_act(self, dygraph):
        x = rand((8, 256), 6)
        for act, ref in [("gelu", lambda v: jax.nn.gelu(v,
                                                        approximate=False)),
                         ("relu", jax.nn.relu)]:
            out = L.fused_act_dropout(to_variable(x), act=act,
                                      dropout_prob=0.5, is_test=True)
            np.testing.assert_allclose(out.numpy(), np.asarray(ref(x)),
                                       rtol=1e-5, atol=1e-6)

    def test_train_structure_and_grad(self, dygraph):
        p = 0.25
        xnp = rand((64, 256), 7)
        x = to_variable(xnp)
        x.stop_gradient = False
        out = L.fused_act_dropout(x, act="relu", dropout_prob=p)
        o = out.numpy()
        pos = xnp > 0
        kept = np.abs(o) > 1e-7
        # survivors are relu(x)/(1-p); relu already zeroes x<=0
        np.testing.assert_allclose(o[kept], (xnp / (1 - p))[kept],
                                   rtol=1e-4)
        assert not np.any(kept & ~pos)
        loss = L.reduce_sum(out)
        loss.backward()
        g = x.gradient()
        np.testing.assert_allclose(g[kept], 1.0 / (1 - p), rtol=1e-4)
        np.testing.assert_allclose(g[~pos], 0.0, atol=1e-7)


class TestEncoderLayerUsesFusion:
    def test_eval_forward_matches_manual(self, dygraph):
        """Post-norm encoder layer in eval mode == hand-computed
        attn/MLP with plain adds (the fused epilogues are exact when
        dropout is off)."""
        from paddle_tpu.nn.layer import TransformerEncoderLayer
        layer = TransformerEncoderLayer(64, 4, 128, dropout=0.1,
                                        activation="gelu")
        layer.eval()
        x = to_variable(rand((2, 8, 64), 8))
        out = layer(x)
        # manual: same sublayers, plain residual adds
        a = layer.self_attn(x, x, x, None)
        h1 = layer.norm1(x + a)
        m = layer.linear2(to_variable(np.asarray(
            jax.nn.gelu(jnp.asarray(layer.linear1(h1).numpy()),
                        approximate=False))))
        ref = layer.norm2(h1 + m)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-4,
                                   atol=2e-5)

    def test_train_forward_backward_finite(self, dygraph):
        from paddle_tpu.nn.layer import TransformerEncoderLayer
        layer = TransformerEncoderLayer(64, 4, 128, dropout=0.1,
                                        activation="gelu")
        layer.train()
        x = to_variable(rand((2, 8, 64), 9))
        x.stop_gradient = False
        loss = L.reduce_mean(layer(x))
        loss.backward()
        assert np.isfinite(float(loss.numpy()))
        assert np.all(np.isfinite(x.gradient()))


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="pallas kernels need the TPU backend")
class TestPallasParity:
    """On-chip: the pallas fused kernels against the jnp reference with a
    shared mask extracted from the kernel's own output."""

    def test_dropout_add_fwd_bwd_mask_identity(self):
        from paddle_tpu.ops.pallas_kernels import fused_dropout_add_tpu
        key = jax.random.PRNGKey(0)
        x = jnp.asarray(rand((128, 256), 10)) + 2.0
        r = jnp.asarray(rand((128, 256), 11))
        p = 0.3

        def f(x, r):
            return fused_dropout_add_tpu(x, r, key, p, True).sum()

        out = fused_dropout_add_tpu(x, r, key, p, True)
        kept = jnp.abs(out - r) > 1e-6
        gx, gr = jax.grad(f, argnums=(0, 1))(x, r)
        # backward regenerated the SAME mask
        np.testing.assert_allclose(np.asarray(gx[kept]), 1 / (1 - p),
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(gx[~kept]), 0.0, atol=1e-7)
        np.testing.assert_allclose(np.asarray(gr),
                                   np.ones(gr.shape, "float32"))

    def test_act_dropout_gelu_matches_exact_erf(self):
        # rate=0 keeps everything: the kernel's polynomial erf must match
        # lax.erf-based gelu (poly |err| <= 1.5e-7) in fwd AND bwd — this
        # is the path that broke on-chip (lax.erf has no Mosaic lowering)
        from paddle_tpu.ops.pallas_kernels import fused_act_dropout_tpu
        key = jax.random.PRNGKey(3)
        x = jnp.asarray(rand((128, 256), 13) * 3.0)
        out = fused_act_dropout_tpu(x, key, 0.0, True, "gelu")
        ref = 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=1e-5)
        g = jax.grad(lambda v: fused_act_dropout_tpu(
            v, key, 0.0, True, "gelu").sum())(x)
        gref = jax.grad(lambda v: (0.5 * v * (1.0 + jax.lax.erf(
            v / np.sqrt(2.0)))).sum())(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                                   atol=2e-6, rtol=1e-5)

    def test_act_dropout_fwd_bwd_mask_identity(self):
        from paddle_tpu.ops.pallas_kernels import fused_act_dropout_tpu
        key = jax.random.PRNGKey(1)
        x = jnp.asarray(rand((128, 256), 12))
        p = 0.25
        out = fused_act_dropout_tpu(x, key, p, True, "relu")
        kept = np.abs(np.asarray(out)) > 1e-7
        g = jax.grad(lambda v: fused_act_dropout_tpu(
            v, key, p, True, "relu").sum())(x)
        g = np.asarray(g)
        np.testing.assert_allclose(g[kept], 1 / (1 - p), rtol=1e-4)
        np.testing.assert_allclose(g[np.asarray(x) <= 0], 0.0, atol=1e-7)
