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


# ---------------------------------------------------------------------------
# a head whose score width differs from its value width (latent attention)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq, score, value, causal, want", [
    (4096, 192, 128, True, "splash_kernel"),    # MLA: 128 + 64 rotary
    (1024, 192, 128, True, "splash_kernel"),
    (4096, 256, 128, True, "splash_kernel"),
    (4096, 192, 256, True, "splash_kernel"),
    (512, 192, 128, True, "xla"),       # ungrouped, under the streaming floor
    (4096, 192, 64, True, "xla"),       # values not whole lane groups
    (4096, 160, 128, True, "xla"),      # scores not whole half groups
    (4096, 64, 128, True, "splash_kernel"),     # differential: 64, 2 x 64
    (4096, 32, 128, True, "xla"),       # scores under half a group
    (4096, 192, 128, False, "xla"),     # no kernel of two widths but splash
    (512, 192, 128, False, "xla"),
])
def test_attention_path_two_widths(seq, score, value, causal, want):
    from paddle_tpu.ops.attention import attention_path, path_at
    from paddle_tpu.ops.registry import KernelSite
    q = jax.ShapeDtypeStruct((1, 32, seq, score), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 32, seq, value), jnp.bfloat16)
    assert attention_path(q, q, v, None, causal, False) == want
    assert path_at(KernelSite(), q, q, v, None, causal, False) == want
    assert path_at(None, q, q, v, None, causal, False) == "xla"


def test_attention_op_two_widths_and_scale():
    """The op with values narrower than the scores and a scale that is not
    1/sqrt(width), forward and gradients, on the XLA path."""
    q, k = _rand(2, 4, 12, 24, scale=0.5), _rand(2, 4, 12, 24, seed=1,
                                                 scale=0.5)
    v = _rand(2, 4, 12, 16, seed=2)
    attrs = {"causal": True, "scale": 0.37}
    ins = {"Q": q, "K": k, "V": v}
    out = _op("fused_multihead_attention", ins, attrs)
    assert out.shape == (2, 4, 12, 16)
    want, ref_vjp = jax.vjp(
        lambda q, k, v: _attention_ref(q, k, v, 0.37, 0), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    cot = _rand(*out.shape, seed=7)
    for got, ref in zip(_op_grads("fused_multihead_attention", ins, attrs,
                                  cot, ["Q", "K", "V"]), ref_vjp(cot)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_splash_kernel_two_widths_in_interpret_mode():
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import pallas_kernels as pk
    q = _rand(1, 2, 256, 192, scale=0.5)
    k, v = _rand(1, 2, 256, 192, seed=1, scale=0.5), \
        _rand(1, 2, 256, 128, seed=2)
    assert pk.splash_attention_supported(q, k, v, None)
    f = lambda q, k, v: pk.splash_attention_tpu(q, k, v, 0.12)
    with pltpu.force_tpu_interpret_mode():
        got, vjp = jax.vjp(f, q, k, v)
        grads = vjp(got)
    want, ref_vjp = jax.vjp(
        lambda q, k, v: _attention_ref(q, k, v, 0.12, 0), q, k, v)
    assert got.shape == (1, 2, 256, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    for a, r in zip(grads, ref_vjp(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("window", [128, 0])
def test_splash_kernel_takes_a_differential_head_in_interpret_mode(window):
    """Scores over 64, values of 128 (a differential pair's two value heads
    side by side), 4 : 2 grouped heads, with a window and without: the
    splash kernel in the interpreter against the dense spelling, forward
    and gradients."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import pallas_kernels as pk
    q = _rand(1, 4, 256, 64, scale=0.5)
    k, v = _rand(1, 2, 256, 64, seed=1, scale=0.5), \
        _rand(1, 2, 256, 128, seed=2)
    assert pk.splash_attention_supported(q, k, v, None)
    f = lambda q, k, v: pk.splash_attention_tpu(q, k, v, 0.125, window)
    with pltpu.force_tpu_interpret_mode():
        got, vjp = jax.vjp(f, q, k, v)
        grads = vjp(got)
    want, ref_vjp = jax.vjp(
        lambda q, k, v: _attention_ref(q, k, v, 0.125, window), q, k, v)
    assert got.shape == (1, 4, 256, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    for a, r in zip(grads, ref_vjp(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("window", [8, 0])
def test_attention_op_differential_head_on_the_xla_path(window):
    """The op over a 16-wide score head and a 32-wide value head, grouped
    and causal: the banded spelling the CPU takes, forward and gradients."""
    q = _rand(2, 4, 24, 16, scale=0.5)
    k, v = _rand(2, 2, 24, 16, seed=1, scale=0.5), _rand(2, 2, 24, 32, seed=2)
    attrs = {"causal": True, "scale": 0.25, "window": window}
    ins = {"Q": q, "K": k, "V": v}
    out = _op("fused_multihead_attention", ins, attrs)
    assert out.shape == (2, 4, 24, 32)
    want, ref_vjp = jax.vjp(
        lambda q, k, v: _attention_ref(q, k, v, 0.25, window), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    cot = _rand(*out.shape, seed=7)
    for got, ref in zip(_op_grads("fused_multihead_attention", ins, attrs,
                                  cot, ["Q", "K", "V"]), ref_vjp(cot)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_latent_attention_equals_an_uncompressed_multi_head_spelling():
    """MLA as the configuration spells it from fluid.layers (latents,
    latent norms, one shared rotary key, concatenated score halves) against
    a head-by-head NumPy multi-head attention whose per-head matrices are
    cut from the same latent matrices."""
    import os
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers as L
    from paddle_tpu.fluid.core import Scope, scope_guard
    from benchmark.harness.registry import Registry, load_module
    reg = Registry()
    cfg, cfg_dir = reg.config("xing4_29b_a4b_train")
    model = load_module(os.path.join(cfg_dir, "model.py"))
    heads, nope, rope, vd, ql, kvl, hidden, seq = 3, 8, 4, 6, 10, 7, 20, 9
    cfg.update(hidden_size=hidden, num_attention_heads=heads,
               num_key_value_heads=heads, qk_nope_head_dim=nope,
               qk_rope_head_dim=rope, v_head_dim=vd, q_lora_rank=ql,
               kv_lora_rank=kvl, num_hidden_layers=1, vocab_size=32,
               intermediate_size=16)
    built = model.build(cfg, {"seq_len": seq, "samples_per_chip": 1},
                        train=False)
    block = built["main"].global_block()
    h_name = next(op.input_arg_names[0] for op in block.ops
                  if op.output_arg_names[0].startswith(
                      "layer_0.attention.q_a."))
    out_name = next(op.output_arg_names[0] for op in block.ops
                    if op.output_arg_names[0].startswith(
                        "layer_0.attention.output."))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 32, (1, seq)).astype("int64")
    exe = fluid.Executor()
    with scope_guard(Scope()):
        built["startup"].random_seed = 3
        exe.run(built["startup"])
        h, got = exe.run(built["main"],
                         feed={"input_ids": ids, "labels": ids},
                         fetch_list=[h_name, out_name])
        w = {n: np.asarray(fluid.global_scope().find_var(
            "layer_0.attention." + n), np.float64)
            for n in ("q_a.w", "q_a_norm.scale", "q_b.w", "kv_a.w",
                      "kv_a_norm.scale", "kv_b.w", "output.w")}
    exe.close()
    h = np.asarray(h, np.float64)[0]                         # [seq, hidden]

    def rms(x, scale):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * scale

    def rot(x):
        inv = np.asarray(model.rotary_frequencies(cfg))
        ang = np.arange(seq)[:, None] * np.concatenate([inv, inv])
        half = x.shape[-1] // 2
        return x * np.cos(ang) + np.concatenate(
            [-x[:, half:], x[:, :half]], -1) * np.sin(ang)

    c_q = rms(h @ w["q_a.w"], w["q_a_norm.scale"])
    c_kv = rms((h @ w["kv_a.w"])[:, :kvl], w["kv_a_norm.scale"])
    k_rope = rot((h @ w["kv_a.w"])[:, kvl:])
    scale = model.softmax_scale(cfg)
    assert scale == pytest.approx((nope + rope) ** -0.5
                                  * (0.1 * math.log(64) + 1) ** 2)
    ctx = []
    for head in range(heads):
        wq = w["q_b.w"][:, head * (nope + rope):(head + 1) * (nope + rope)]
        wkv = w["kv_b.w"][:, head * (nope + vd):(head + 1) * (nope + vd)]
        q = np.concatenate([c_q @ wq[:, :nope], rot(c_q @ wq[:, nope:])], -1)
        k = np.concatenate([c_kv @ wkv[:, :nope], k_rope], -1)
        scores = q @ k.T * scale
        scores[np.triu_indices(seq, 1)] = -np.inf
        p = np.exp(scores - scores.max(-1, keepdims=True))
        ctx.append(p / p.sum(-1, keepdims=True) @ (c_kv @ wkv[:, nope:]))
    want = np.concatenate(ctx, -1) @ w["output.w"]
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-4,
                               atol=2e-6)


# ---------------------------------------------------------------------------
# the mixer of a multi-stream residual path
# ---------------------------------------------------------------------------

def _mixer_numpy(x, phi, alpha, b, n, iters, hc_eps, clamp, eps=1e-6):
    """x [T, n, d] -> (y, post, C) in float64."""
    x = np.asarray(x, np.float64)
    flat = x.reshape(x.shape[0], -1)
    m = flat @ np.asarray(phi, np.float64) \
        / np.sqrt((flat * flat).mean(-1, keepdims=True) + eps)
    sig = lambda a: 1.0 / (1.0 + np.exp(-a))
    pre = sig(alpha[0] * m[:, :n] + b[:n])
    post = 2.0 * sig(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    c = np.exp(np.clip(alpha[2] * m[:, 2 * n:] + b[2 * n:], *clamp))
    c = c.reshape(-1, n, n)
    for _ in range(iters):
        c = c / (c.sum(-1, keepdims=True) + hc_eps)
        c = c / (c.sum(-2, keepdims=True) + hc_eps)
    return np.einsum("tn,tnd->td", pre, x), post, c


_MIX_ATTRS = {"n": 4, "epsilon": 1e-6, "sinkhorn_iters": 20, "hc_eps": 1e-6,
              "clamp_min": -30.0, "clamp_max": 30.0}


def _mixer_inputs(alpha=(1.0, 0.7, 1.3), t=10, d=8, seed=0):
    n = 4
    return {"X": _rand(t, n * d, seed=seed),
            "Phi": _rand(n * d, n * n + 2 * n, seed=seed + 1, scale=0.3),
            "Alpha": jnp.asarray(alpha, jnp.float32),
            "B": _rand(n * n + 2 * n, seed=seed + 2, scale=0.5)}


def test_mixer_equals_a_numpy_spelling_and_c_is_doubly_stochastic():
    ins = _mixer_inputs()
    out = get_op("hyper_connection_mix").fn(
        {k: [v] for k, v in ins.items()}, _MIX_ATTRS, CTX)
    y, post, c = _mixer_numpy(
        np.asarray(ins["X"]).reshape(10, 4, 8), ins["Phi"],
        np.asarray(ins["Alpha"]), np.asarray(ins["B"]), 4, 20, 1e-6,
        (-30.0, 30.0))
    np.testing.assert_allclose(np.asarray(out["Y"][0]), y, rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(out["Post"][0]), post, rtol=2e-5)
    got_c = np.asarray(out["C"][0]).reshape(10, 4, 4)
    np.testing.assert_allclose(got_c, c, rtol=1e-4, atol=1e-6)
    # columns last: they add up to one exactly, the rows after 20
    # iterations to a few 1e-3 for a random start
    np.testing.assert_allclose(got_c.sum(-2), 1.0, atol=1e-5)
    row_error = np.abs(got_c.sum(-1) - 1.0).max()
    assert row_error < 1e-2
    assert float(out["RowSumError"][0][0]) == pytest.approx(row_error,
                                                            rel=1e-3)
    # one iteration is not enough: the gauge would show it
    once = get_op("hyper_connection_mix").fn(
        {k: [v] for k, v in ins.items()},
        dict(_MIX_ATTRS, sinkhorn_iters=1), CTX)
    assert float(once["RowSumError"][0][0]) > 10 * row_error


def test_mixer_and_merge_gradients_equal_jax_of_the_numpy_spelling():
    ins = _mixer_inputs(seed=3)
    z = _rand(10, 8, seed=9)

    def spelled(x, phi, alpha, b, z):
        flat = x
        m = (flat @ phi) * jax.lax.rsqrt(
            jnp.mean(flat * flat, -1, keepdims=True) + 1e-6)
        pre = jax.nn.sigmoid(alpha[0] * m[:, :4] + b[:4])
        post = 2 * jax.nn.sigmoid(alpha[1] * m[:, 4:8] + b[4:8])
        c = jnp.exp(jnp.clip(alpha[2] * m[:, 8:] + b[8:], -30, 30))
        c = c.reshape(-1, 4, 4)
        for _ in range(20):
            c = c / (c.sum(-1, keepdims=True) + 1e-6)
            c = c / (c.sum(-2, keepdims=True) + 1e-6)
        xs = x.reshape(-1, 4, 8)
        y = jnp.einsum("tn,tnd->td", pre, xs)
        new = post[..., None] * (z + jnp.tanh(y))[:, None, :] \
            + jnp.einsum("tij,tjd->tid", c, xs)
        return new.reshape(x.shape)

    def through_ops(x, phi, alpha, b, z):
        mixed = get_op("hyper_connection_mix").fn(
            {"X": [x], "Phi": [phi], "Alpha": [alpha], "B": [b]},
            _MIX_ATTRS, CTX)
        return get_op("hyper_connection_merge").fn(
            {"X": [x], "Z": [z + jnp.tanh(mixed["Y"][0])],
             "Post": mixed["Post"], "C": mixed["C"]}, {}, CTX)["Out"][0]

    args = (ins["X"], ins["Phi"], ins["Alpha"], ins["B"], z)
    with jax.default_matmul_precision("highest"):
        want, ref_vjp = jax.vjp(spelled, *args)
        got, vjp = jax.vjp(through_ops, *args)
        cot = _rand(*want.shape, seed=5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        for a, r in zip(vjp(cot), ref_vjp(cot)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-3, atol=2e-5)


def test_mixer_starts_as_the_plain_residual():
    """With the layer's default parameters (pre = 1/n, post = 1, a diagonal
    of 8) equal streams stay equal and each becomes stream + branch."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers as L
    from paddle_tpu.fluid.core import Scope, scope_guard
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        e = fluid.data("e", [-1, 6, 8], dtype="float32")
        stream = L.expand(e, [1, 1, 4])
        y, post, c = L.hyper_connection_mix(stream, 4, name="l0.attn")
        branch = L.scale(y, scale=3.0)
        out = L.hyper_connection_merge(stream, branch, post, c)
    ev = np.random.RandomState(1).randn(2, 6, 8).astype("float32")
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(startup)
        yv, cv, ov = exe.run(main, feed={"e": ev}, fetch_list=[y, c, out])
        err = np.asarray(fluid.global_scope().find_var(
            "l0.attn.res_row_sum_error"))
    exe.close()
    # alpha starts at 0.01, not 0: the token moves its gates by a percent
    np.testing.assert_allclose(yv, ev, rtol=3e-2, atol=1e-3)
    np.testing.assert_allclose(np.asarray(cv).reshape(2, 6, 4, 4),
                               np.broadcast_to(np.eye(4), (2, 6, 4, 4)),
                               atol=2e-3)
    for i in range(4):
        np.testing.assert_allclose(ov[..., i * 8:(i + 1) * 8], ev + 3 * ev,
                                   rtol=5e-2, atol=5e-3)
    assert err.shape == (1,) and 0 <= err[0] < 1e-4


# ---------------------------------------------------------------------------
# the mixers' Pallas kernels (ops/pallas_kernels.py) against the jnp spelling
# of the two lowerings above, under the TPU interpreter
# ---------------------------------------------------------------------------

_MIXER_OUTS = ("Y", "Post", "C", "RowSumError", "Out")
_MIXER_GRADS = ("dX", "dPhi", "dAlpha", "dB", "dZ", "dPost", "dC")


@pytest.fixture(scope="module")
def mixer_kernels_and_spelling():
    """{name: (kernel's, spelling's)} for every output and gradient of a
    mix + merge at n = 4, d = 256, 2 x 40 tokens in tiles of 32: the last
    tile is ragged, and what the interpreter pads it with must reach
    neither dPhi nor RowSumError."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import pallas_kernels as pk
    n, d = 4, 256
    ins = _mixer_inputs(t=80, d=d, seed=11)
    ins["Phi"] = ins["Phi"] * 0.2
    x3 = ins["X"].reshape(2, 40, n * d)
    z = _rand(2, 40, d, seed=12).astype(jnp.bfloat16)

    def spelled(x, phi, alpha, b, z):
        mixed = get_op("hyper_connection_mix").fn(
            {"X": [x], "Phi": [phi], "Alpha": [alpha], "B": [b]},
            _MIX_ATTRS, CTX)
        y, post, c = (mixed[s][0] for s in ("Y", "Post", "C"))
        # post and c reach the result twice: through the merge and as they
        # are, so that dPost and dC of the merge are in the gradient
        out = get_op("hyper_connection_merge").fn(
            {"X": [x], "Z": [z], "Post": [post * 1.0], "C": [c * 1.0]},
            {}, CTX)["Out"][0]
        return y, post, c, mixed["RowSumError"][0], out

    def kernels(x, phi, alpha, b, z):
        rows = x.reshape(-1, n * d)
        y, post, c, err = pk.hyper_connection_mix_tpu(
            rows, phi, alpha, b, n, 1e-6, 20, 1e-6, (-30.0, 30.0), tile=32)
        out = pk.hyper_connection_merge_tpu(
            rows, z.reshape(-1, d), post * 1.0, c * 1.0, tile=32)
        return (y.reshape(2, 40, d), post.reshape(2, 40, n),
                c.reshape(2, 40, n * n), err, out.reshape(x.shape))

    args = (x3, ins["Phi"], ins["Alpha"], ins["B"], z)
    with jax.default_matmul_precision("highest"):
        want, ref_vjp = jax.vjp(spelled, *args)
        cot = tuple(_rand(*w.shape, seed=20 + i).astype(w.dtype)
                    for i, w in enumerate(want))
        want_g = ref_vjp(cot)
        with pltpu.force_tpu_interpret_mode():
            got, vjp = jax.vjp(kernels, *args)
            got_g = vjp(cot)

        # the merge's own gradients with respect to Post and C
        def merge_of(fn):
            return jax.vjp(fn, want[1], want[2])[1](cot[4])
        want_pc = merge_of(lambda p, c: get_op("hyper_connection_merge").fn(
            {"X": [x3], "Z": [z], "Post": [p], "C": [c]}, {}, CTX)["Out"][0])
        with pltpu.force_tpu_interpret_mode():
            got_pc = merge_of(lambda p, c: pk.hyper_connection_merge_tpu(
                x3.reshape(-1, n * d), z.reshape(-1, d), p.reshape(-1, n),
                c.reshape(-1, n * n), tile=32).reshape(x3.shape))
    pairs = dict(zip(_MIXER_OUTS, zip(got, want)))
    pairs.update(zip(_MIXER_GRADS[:5], zip(got_g, want_g)))
    pairs.update(zip(_MIXER_GRADS[5:], zip(got_pc, want_pc)))
    return pairs


@pytest.mark.parametrize("name", _MIXER_OUTS + _MIXER_GRADS)
def test_mixer_kernels_equal_the_jnp_spelling(mixer_kernels_and_spelling,
                                              name):
    got, want = mixer_kernels_and_spelling[name]
    assert got.shape == want.shape and got.dtype == want.dtype, name
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all(), name
    # float32 sums in another order; dZ is rounded to the branch's bfloat16
    tol = 1e-2 if name == "dZ" else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


def test_mixer_lowerings_take_the_kernels_where_the_context_allows():
    """Through the two op lowerings with a context that answers as the chip
    does: [2, 128, 4 x 128] streams are two whole tiles, the kernels run
    (under the interpreter), the counter says so and the outputs keep the
    ops' shapes."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.fluid import trace

    class OnTheChip(LoweringContext):
        def pallas_ok(self):
            return True

    ins = _mixer_inputs(t=256, d=128, seed=4)
    x3 = ins["X"].reshape(2, 128, 512)
    z = _rand(2, 128, 128, seed=5)

    def run(ctx):
        mixed = get_op("hyper_connection_mix").fn(
            {"X": [x3], "Phi": [ins["Phi"]], "Alpha": [ins["Alpha"]],
             "B": [ins["B"]]}, _MIX_ATTRS, ctx)
        out = get_op("hyper_connection_merge").fn(
            {"X": [x3], "Z": [z], "Post": mixed["Post"], "C": mixed["C"]},
            {}, ctx)["Out"][0]
        return [mixed[s][0] for s in ("Y", "Post", "C", "RowSumError")] \
            + [out]

    counters = {p: trace.metrics().counter(f"hyper_connection.lowering.{p}")
                for p in ("pallas", "xla")}
    before = {p: c.value for p, c in counters.items()}
    with jax.default_matmul_precision("highest"):
        want = run(CTX)
        with pltpu.force_tpu_interpret_mode():
            got = run(OnTheChip(base_key=jax.random.PRNGKey(0)))
    assert {p: c.value - before[p] for p, c in counters.items()} \
        == {"pallas": 2, "xla": 2}
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)


def test_mixer_lowerings_count_xla_on_the_cpu():
    from paddle_tpu.fluid import trace
    counters = {p: trace.metrics().counter(f"hyper_connection.lowering.{p}")
                for p in ("pallas", "xla")}
    before = {p: c.value for p, c in counters.items()}
    ins = _mixer_inputs(t=128, d=128)
    mixed = get_op("hyper_connection_mix").fn(
        {k: [v] for k, v in ins.items()}, _MIX_ATTRS, CTX)
    get_op("hyper_connection_merge").fn(
        {"X": [ins["X"]], "Z": [_rand(128, 128)], "Post": mixed["Post"],
         "C": mixed["C"]}, {}, CTX)
    assert {p: c.value - before[p] for p, c in counters.items()} \
        == {"pallas": 0, "xla": 2}


@pytest.mark.parametrize("dtype, tokens, d, n, want", [
    ("float32", 4096, 3584, 4, True),      # the cell's streams
    ("float32", 4096, 3584, 2, True),
    ("bfloat16", 4096, 3584, 4, False),    # the streams are float32
    ("float32", 4096, 200, 4, False),      # a stream is whole lane groups
    ("float32", 4096, 200, 2, False),
    ("float32", 4096 + 40, 3584, 4, True),  # the last tile may be ragged
    ("float32", 80, 256, 4, False),        # at least one tile of tokens
    ("float32", 256, 16384, 4, False),     # a tile past the VMEM budget
    ("float32", 256, 128, 10, False),      # 131 rows in one transpose
])
def test_hyper_connection_supported(dtype, tokens, d, n, want):
    from paddle_tpu.ops.pallas_kernels import hyper_connection_supported
    x = jax.ShapeDtypeStruct((1, tokens, n * d), jnp.dtype(dtype))
    assert hyper_connection_supported(x, n) is want
