"""The ops of a causal decoder block (ops/decoder_ops.py, and the attention
op's ``causal`` / ``window`` / grouped heads) against plain jnp spellings,
forward and gradient, through the op registry and ``generic_grad``."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401 — registers the lowerings
from paddle_tpu.fluid.backward import _generic_grad
from paddle_tpu.ops.registry import LoweringContext, get_op

CTX = LoweringContext(base_key=jax.random.PRNGKey(0))


def _rand(*shape, seed=0, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _op(op_type, ins, attrs, out_slot="Out"):
    return get_op(op_type).fn({k: [v] for k, v in ins.items()}, attrs,
                              CTX)[out_slot][0]


def _op_grads(op_type, ins, attrs, cot, grad_slots, out_slot="Out"):
    g_ins = {"I_" + s: [v] for s, v in ins.items()}
    g_ins["G_" + out_slot] = [cot]
    got = _generic_grad(g_ins, {"fwd_type": op_type, "fwd_attrs": attrs,
                                "in_slots": list(ins),
                                "grad_slots": list(grad_slots)}, CTX)
    return [got["GI_" + s][0] for s in grad_slots]


def _check(op_type, ins, attrs, ref, grad_slots, out_slot="Out", tol=2e-5):
    """The op's output and its generic_grad gradients against ``ref`` (a
    function of the grad slots' arrays) and jax's gradients of it."""
    out = _op(op_type, ins, attrs, out_slot)
    want = ref(*[ins[s] for s in grad_slots])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol)
    cot = _rand(*out.shape, seed=7)
    got = _op_grads(op_type, ins, attrs, cot, grad_slots, out_slot)
    _, vjp = jax.vjp(ref, *[ins[s] for s in grad_slots])
    for name, a, r in zip(grad_slots, got, vjp(cot)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=tol,
                                   atol=tol, err_msg=name)


def test_rms_norm():
    x, scale = _rand(3, 5, 16), 1.0 + 0.1 * _rand(16, seed=1)
    _check("rms_norm", {"X": x, "Scale": scale}, {"epsilon": 1e-6},
           lambda x, s: x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + 1e-6) * s,
           ["X", "Scale"], out_slot="Y")


def test_rms_norm_keeps_bf16_in_and_float32_statistics():
    x = _rand(4, 256).astype(jnp.bfloat16)
    y = _op("rms_norm", {"X": x, "Scale": jnp.ones(256)}, {}, "Y")
    assert y.dtype == jnp.bfloat16
    xf = x.astype(jnp.float32)
    want = xf / jnp.sqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(want),
                               rtol=1e-2)


def _yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """transformers' _compute_yarn_parameters, in numpy."""
    pos_freqs = base ** (np.arange(0, dim, 2) / dim)

    def correction(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (1 / (factor * pos_freqs)) * ramp + (1 / pos_freqs) * (1 - ramp)


@pytest.mark.parametrize("kind", ["default", "yarn"])
def test_rotary_embedding_both_frequency_sets(kind):
    from benchmark.harness.registry import Registry, load_module
    import os
    reg = Registry()
    cfg, cfg_dir = reg.config("mellum2_12b_a2_5b_train")
    model = load_module(os.path.join(cfg_dir, "model.py"))
    layer_type = {"default": "sliding_attention",
                  "yarn": "full_attention"}[kind]
    freqs, factor = model.rotary_frequencies(cfg, layer_type)
    if kind == "yarn":
        rope = cfg["rope_parameters"]["full_attention"]
        np.testing.assert_allclose(
            freqs, _yarn(128, 500000.0, 16, 8192, 32, 1), rtol=1e-12)
        assert factor == rope["attention_factor"]
        assert abs(factor - (0.1 * math.log(16) + 1)) < 1e-12
        # the fastest dimensions keep their frequency, the slowest are / 16
        assert freqs[0] == 1.0 and abs(
            freqs[-1] * 16 - 500000.0 ** (-126 / 128)) < 1e-12
    else:
        np.testing.assert_allclose(
            freqs, 500000.0 ** (-np.arange(0, 128, 2) / 128), rtol=1e-12)
        assert factor == 1.0
    x = _rand(2, 3, 24, 128)
    inv = jnp.asarray(freqs, jnp.float32)

    def ref(x):
        angle = jnp.arange(24.0)[:, None] * jnp.concatenate([inv, inv])
        rot = jnp.concatenate([-x[..., 64:], x[..., :64]], -1)
        return x * jnp.cos(angle) * factor + rot * jnp.sin(angle) * factor
    _check("rotary_embedding", {"X": x},
           {"inv_freq": list(freqs), "scale": factor}, ref, ["X"])


def test_swiglu():
    _check("swiglu", {"X": _rand(6, 8), "Y": _rand(6, 8, seed=1)}, {},
           lambda x, y: jax.nn.silu(x) * y, ["X", "Y"])


def _attention_ref(q, k, v, scale, window):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = q.shape[2]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = j <= i
    if window:
        keep &= i - j < window
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("seq, window, heads, kv_heads", [
    (12, 0, 4, 4),      # causal: the lowering the op already had
    (6, 8, 4, 4),       # shorter than the window: plain causal
    (8, 8, 4, 4),       # exactly the window
    (24, 8, 4, 4),      # longer than the window
    (24, 0, 8, 1),      # eight query heads to a key/value head
    (24, 8, 8, 2),      # both
])
def test_attention_op_causal_window_grouped(seq, window, heads, kv_heads):
    q = _rand(2, heads, seq, 16)
    k, v = _rand(2, kv_heads, seq, 16, seed=1), _rand(2, kv_heads, seq, 16,
                                                       seed=2)
    _check("fused_multihead_attention", {"Q": q, "K": k, "V": v},
           {"scale": 0.25, "causal": True, "window": window,
            "num_kv_heads": kv_heads},
           lambda q, k, v: _attention_ref(q, k, v, 0.25, window),
           ["Q", "K", "V"])


def test_banded_attention_in_blocks_equals_one_block(monkeypatch):
    """Several query blocks, each against the keys of its band only."""
    from paddle_tpu.ops import attention
    q, k, v = _rand(1, 8, 64, 16), _rand(1, 2, 64, 16, seed=1), \
        _rand(1, 2, 64, 16, seed=2)
    monkeypatch.setattr(attention, "_BAND_BLOCK", 16)
    for window in (0, 8, 24):
        f = lambda q, k, v: attention._banded_attention(q, k, v, 0.25, window)
        got, vjp = jax.vjp(f, q, k, v)
        want, ref_vjp = jax.vjp(
            lambda q, k, v: _attention_ref(q, k, v, 0.25, window), q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        for a, r in zip(vjp(want), ref_vjp(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-5, atol=2e-5)


def test_window_needs_causal_and_no_mask():
    from paddle_tpu.ops.attention import flash_attention
    q = _rand(1, 2, 8, 16)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=4)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q[:, :1], q[:, :1], causal=True,
                        mask=jnp.zeros((1, 1, 1, 8)))


@pytest.mark.parametrize("seq, d, heads, kv_heads, window, causal, want", [
    (8192, 128, 32, 4, 1024, True, "splash_kernel"),
    (8192, 128, 32, 4, 0, True, "splash_kernel"),
    (2048, 128, 8, 8, 0, True, "splash_kernel"),    # long causal, ungrouped
    (512, 128, 8, 1, 0, True, "splash_kernel"),     # grouped at any length
    (512, 64, 8, 1, 0, True, "xla"),                # head not lane-aligned
    (200, 128, 8, 1, 64, True, "xla"),              # not in whole blocks
    (512, 64, 12, 12, 0, False, "fused_kernel"),    # BERT's: as before
    (1024, 64, 12, 12, 0, False, "flash_kernel"),
])
def test_attention_path(seq, d, heads, kv_heads, window, causal, want):
    from paddle_tpu.ops.attention import attention_path, path_at
    from paddle_tpu.ops.registry import KernelSite
    q = jax.ShapeDtypeStruct((1, heads, seq, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, kv_heads, seq, d), jnp.bfloat16)
    assert attention_path(q, k, k, None, causal, False,
                          window=window) == want
    assert path_at(KernelSite(), q, k, k, None, causal, False,
                   window=window) == want
    assert path_at(None, q, k, k, None, causal, False,
                   window=window) == "xla"


def test_lowering_counters_name_the_mask():
    from paddle_tpu.fluid import trace
    from paddle_tpu.ops.attention import flash_attention

    def count(name):
        return trace.metrics().counter("attention.lowering." + name).value
    before = {n: count(n) for n in ("xla", "xla.window", "xla.full_causal")}
    q = _rand(1, 2, 8, 16)
    flash_attention(q, q, q, causal=True, window=4)
    flash_attention(q, q, q, causal=True)
    flash_attention(q, q, q)
    assert count("xla") - before["xla"] == 3
    assert count("xla.window") - before["xla.window"] == 1
    assert count("xla.full_causal") - before["xla.full_causal"] == 1


def test_splash_kernel_numerics_in_interpret_mode():
    """The kernel behind the dispatcher, window and grouped heads, forward
    and gradient, in the Pallas interpreter."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import pallas_kernels as pk
    q = _rand(1, 4, 256, 128, scale=0.5)
    k, v = _rand(1, 2, 256, 128, seed=1, scale=0.5), \
        _rand(1, 2, 256, 128, seed=2)
    assert pk.splash_attention_supported(q, k, v, None)
    for window in (0, 100):
        f = lambda q, k, v: pk.splash_attention_tpu(q, k, v, 0.1, window)
        with pltpu.force_tpu_interpret_mode():
            got, vjp = jax.vjp(f, q, k, v)
            grads = vjp(got)
        want, ref_vjp = jax.vjp(
            lambda q, k, v: _attention_ref(q, k, v, 0.1, window), q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)
        for a, r in zip(grads, ref_vjp(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=5e-3, atol=5e-3)
