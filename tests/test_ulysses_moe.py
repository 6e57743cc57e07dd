"""All-to-all sequence parallelism (Ulysses) over the 8-virtual-device CPU
mesh — a long-context capability beyond the reference (SURVEY §2.9 'NOT
PRESENT' row).  The sparse-expert layer's tests are tests/test_moe.py."""
import math

import numpy as np
import pytest
pytestmark = pytest.mark.slow


import jax
import jax.numpy as jnp
from paddle_tpu.parallel.api import compat_shard_map as shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel import mesh as pmesh
from paddle_tpu.parallel.ulysses import ulysses_attention


def _reference_attention(q, k, v, scale, causal=False):
    s = np.einsum("bhqd,bhkd->bhqk", q, k).astype(np.float32) * scale
    if causal:
        t = q.shape[-2]
        s = np.where(np.tril(np.ones((t, t), bool))[None, None], s, -1e30)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v.astype(np.float32))


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        mesh = pmesh.build_mesh({"sp": 4})
        try:
            b, h, t, d = 2, 8, 16, 4
            rng = np.random.RandomState(0)
            q = rng.randn(b, h, t, d).astype("float32")
            k = rng.randn(b, h, t, d).astype("float32")
            v = rng.randn(b, h, t, d).astype("float32")
            scale = 1.0 / math.sqrt(d)

            f = shard_map(
                lambda q, k, v: ulysses_attention(q, k, v, "sp",
                                                  causal=causal),
                mesh=mesh, in_specs=P(None, None, "sp", None),
                out_specs=P(None, None, "sp", None))
            got = np.asarray(jax.jit(f)(q, k, v))
            ref = _reference_attention(q, k, v, scale, causal)
            np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
        finally:
            pmesh.set_current_mesh(None)

    def test_rejects_indivisible_heads(self):
        mesh = pmesh.build_mesh({"sp": 4})
        try:
            q = jnp.zeros((1, 6, 8, 4))     # 6 heads not divisible by 4
            f = shard_map(
                lambda q: ulysses_attention(q, q, q, "sp"),
                mesh=mesh, in_specs=P(None, None, "sp", None),
                out_specs=P(None, None, "sp", None))
            with pytest.raises(ValueError, match="divisible"):
                f(q)
        finally:
            pmesh.set_current_mesh(None)


class TestHybridUlyssesMode:
    def test_ulysses_sp_matches_ring_sp(self):
        """The hybrid transformer trains identically under sp_mode='ring'
        and 'ulysses' — both are exact attention, just different comm
        schedules."""
        from paddle_tpu.parallel.hybrid import (TransformerConfig,
                                                build_hybrid_mesh,
                                                demo_batch, make_train_step)

        def run(sp_mode):
            mesh = build_hybrid_mesh(
                8, axes={"dp": 1, "pp": 2, "tp": 2, "sp": 2})
            cfg = TransformerConfig(n_layers=2, seq_len=32, batch=8,
                                    microbatches=2, sp_mode=sp_mode)
            params, opt, step = make_train_step(mesh, cfg)
            tok, lbl = demo_batch(cfg, mesh, seed=3)
            losses = []
            for _ in range(3):
                params, opt, loss = step(params, opt, tok, lbl)
                losses.append(float(loss))
            return losses

        ring = run("ring")
        uly = run("ulysses")
        np.testing.assert_allclose(uly, ring, rtol=2e-4, atol=2e-5)
        assert uly[-1] < uly[0]

    def test_unknown_sp_mode_rejected(self):
        from paddle_tpu.parallel.hybrid import (TransformerConfig,
                                                build_hybrid_mesh,
                                                demo_batch, make_train_step)
        mesh = build_hybrid_mesh(8, axes={"dp": 1, "pp": 1, "tp": 1,
                                          "sp": 8})
        cfg = TransformerConfig(n_layers=1, seq_len=32, batch=8, n_heads=8,
                                microbatches=1, sp_mode="Ulysses")  # typo
        params, opt, step = make_train_step(mesh, cfg)
        tok, lbl = demo_batch(cfg, mesh, seed=0)
        with pytest.raises(ValueError, match="unknown sp_mode"):
            step(params, opt, tok, lbl)
