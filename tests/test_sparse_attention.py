"""Attention over a learned per-query key set (ops/sparse_attention.py):
the index scores, the exact top-k with ties and with rows shorter than
``topk``, the selected attention against a gather-based spelling (forward
and gradients, the ``jnp`` path and the Pallas kernel in the interpreter),
the indexer's loss and its own gradients (the ``jnp`` path and the Pallas
kernels in the interpreter), the Program ops and their layers,
``attention_path``'s fifth answer and ``rotary_embedding`` under three
unequal rows of positions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import sparse_attention as sa
from paddle_tpu.ops.registry import KernelSite, LoweringContext, get_op

HI, DI = 3, 8


def _indexer(seq, seed=0, quantum=None):
    r = np.random.RandomState(seed)
    qi = r.randn(HI, seq, DI).astype("float32")
    ki = r.randn(seq, DI).astype("float32")
    w = r.randn(seq, HI).astype("float32")
    if quantum:         # coarse values: many equal scores
        qi, ki, w = (np.round(a / quantum) * quantum for a in (qi, ki, w))
    return jnp.asarray(qi), jnp.asarray(ki), jnp.asarray(w)


def _scores(qi, ki, w):
    """The index scores spelled directly: [S, S]."""
    z = np.einsum("hrd,nd->hrn", np.asarray(qi, np.float64),
                  np.asarray(ki, np.float64))
    return np.einsum("hrn,rh->rn", np.maximum(z, 0), np.asarray(w, np.float64))


def _oracle_selection(scores, topk):
    """Per row the min(t + 1, topk) largest of s <= t, ties to the smaller
    s, by a stable sort."""
    seq = scores.shape[0]
    sel = np.zeros((seq, seq), np.int8)
    for t in range(seq):
        order = np.argsort(-scores[t, :t + 1], kind="stable")
        sel[t, order[:min(t + 1, topk)]] = 1
    return sel


@pytest.fixture
def small_blocks(monkeypatch):
    """Several super blocks and several blocks in each at 64 queries."""
    monkeypatch.setattr(sa, "_SUPER_ROWS", 32)
    monkeypatch.setattr(sa, "_ROWS", 8)


def test_index_scores_equal_the_direct_spelling():
    qi, ki, w = _indexer(24)
    got = sa.index_scores(qi, ki, w)
    np.testing.assert_allclose(got, _scores(qi, ki, w), rtol=1e-5, atol=1e-5)
    assert got.dtype == jnp.float32
    half = sa.index_scores(qi.astype(jnp.bfloat16), ki.astype(jnp.bfloat16), w)
    assert half.dtype == jnp.float32


@pytest.mark.parametrize("values", [
    [0.0, -0.0, 1.5, -1.5, 3e38, -3e38, 1e-40, -1e-40, np.inf, -np.inf]])
def test_ordered_keys_keep_the_floats_order(values):
    x = jnp.asarray(values, jnp.float32)
    keys = np.asarray(sa.ordered_keys(x))
    order = np.argsort(np.asarray(x), kind="stable")
    assert (np.diff(keys[order]) >= 0).all()
    assert keys[0] == keys[1]                        # -0.0 is 0.0


@pytest.mark.parametrize("k", [1, 2, 7, 16])
def test_kth_largest_is_exact(k):
    r = np.random.RandomState(k)
    x = r.randn(5, 16).astype("float32")
    x[1, :8] = x[1, 8:]                              # pairs of equal values
    x[2] = -np.abs(x[2])                             # all negative
    keys = sa.ordered_keys(jnp.asarray(x))
    got = sa.kth_largest(keys, jnp.full((5,), k, jnp.int32))
    want = np.sort(np.asarray(keys), axis=1)[:, ::-1][:, k - 1]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seq,topk,quantum", [
    (64, 8, None), (64, 64, None), (64, 200, None),     # rows shorter than k
    (64, 8, 0.5), (64, 1, None), (48, 5, 1.0)])
def test_selection_is_the_exact_top_k_with_ties_to_the_smaller_key(
        small_blocks, seq, topk, quantum):
    qi, ki, w = _indexer(seq, seed=topk, quantum=quantum)
    packed = sa.select_topk(qi, ki, w, topk)
    assert packed.dtype == jnp.uint8 and packed.shape == (seq, seq // 8)
    got = np.asarray(sa.unpack_selection(packed)).astype(np.int8)
    scores = np.asarray(sa.index_scores(qi, ki, w))     # the values compared
    want = _oracle_selection(scores, topk)
    if quantum:         # the case is about ties: there must be some
        tied = sum(len(np.unique(scores[t, :t + 1])) < t + 1
                   for t in range(seq))
        assert tied > seq // 2
    np.testing.assert_array_equal(got, want)
    assert (got.sum(1) == np.minimum(np.arange(seq) + 1, topk)).all()
    assert not np.triu(got, 1).any()


def test_selection_gauges_count_keys_and_tiles():
    seq, topk = 1024, 16
    qi, ki, w = _indexer(seq, seed=3)
    sel = sa.select_topk(qi, ki, w, topk)[None]
    mean, tiles = sa.selection_gauges(sel)
    assert float(mean) == pytest.approx(
        (topk * (topk + 1) / 2 + (seq - topk) * topk) / seq)
    assert float(tiles) == 1.0                       # random keys: every tile
    own = sa.pack_selection(jnp.eye(seq, dtype=bool))  # each its own key
    assert float(sa.selection_gauges(own[None])[1]) \
        == pytest.approx(2 / 3)                      # the diagonal tiles only


def _attention_operands(seq, hq=4, hkv=2, d=8, seed=0, batch=2):
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(batch, hq, seq, d).astype("float32"))
    k = jnp.asarray(r.randn(batch, hkv, seq, d).astype("float32"))
    v = jnp.asarray(r.randn(batch, hkv, seq, d).astype("float32"))
    sel = jnp.stack([sa.select_topk(*_indexer(seq, seed + i), 5)
                     for i in range(batch)])
    return q, k, v, sel


def _gathered_attention(q, k, v, sel, scale):
    """Each query against ITS keys, gathered: no mask anywhere."""
    b, hq, seq, d = q.shape
    group = hq // k.shape[1]
    out = []
    for i in range(b):
        rows = []
        for t in range(seq):
            keys = jnp.nonzero(np.asarray(sa.unpack_selection(sel[i, t])))[0]
            kt = jnp.repeat(k[i][:, keys], group, axis=0)       # [hq, n, d]
            vt = jnp.repeat(v[i][:, keys], group, axis=0)
            p = jax.nn.softmax(jnp.einsum("hd,hnd->hn", q[i, :, t], kt)
                               * scale, axis=-1)
            rows.append(jnp.einsum("hn,hnd->hd", p, vt))
        out.append(jnp.stack(rows, axis=1))
    return jnp.stack(out)


def test_selected_attention_equals_the_gathered_spelling(small_blocks):
    q, k, v, sel = _attention_operands(64)
    scale = 8 ** -0.5

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, sel, scale)))
    got, lse = sa.selected_attention(q, k, v, sel, scale)
    want = _gathered_attention(q, k, v, sel, scale)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the log-sum-exp of every query's scores over its own keys
    keep = sa.unpack_selection(sel)[:, None]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1)) * scale
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1),
        rtol=1e-5, atol=1e-5)
    g_got = jax.grad(loss(lambda *a: sa.selected_attention(*a)[0]),
                     (0, 1, 2))(q, k, v)
    g_want = jax.grad(loss(_gathered_attention), (0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_selected_kernel_in_interpret_mode_equals_the_jnp_path():
    """The Pallas lowering (three kernels: forward, dq, dk/dv; the tile of
    the selection shared by the heads of a key/value group) against the
    ``jnp`` path, forward and gradients, grouped heads, two tiles a side."""
    from paddle_tpu.ops import pallas_kernels as pk
    seq, d = 1024, 128
    r = np.random.RandomState(1)
    q = jnp.asarray(r.randn(1, 4, seq, d).astype("float32"))
    k = jnp.asarray(r.randn(1, 2, seq, d).astype("float32"))
    v = jnp.asarray(r.randn(1, 2, seq, d).astype("float32"))
    sel = sa.select_topk(*_indexer(seq, 2), 64)[None]
    assert pk.selected_attention_supported(q, k, v, sel)
    scale = d ** -0.5

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    with pltpu.force_tpu_interpret_mode():
        got, lse = pk.selected_attention_tpu(q, k, v, sel, scale)
        g_got = jax.grad(loss(lambda *a: pk.selected_attention_tpu(
            *a, sel, scale)[0]), (0, 1, 2))(q, k, v)
        # the loss's probabilities, all the heads averaged on the core
        qg = (q[0] * scale).reshape(2, 2, seq, d)
        p_got = pk.selected_probability_mean_tpu(
            qg, k[0], lse[0].reshape(2, 2, seq), sel[0], 1.0, 512, 512, 1024)
    want, want_lse = sa.selected_attention(q, k, v, sel, scale)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(lse, want_lse, rtol=1e-4, atol=1e-4)
    p_want = sa.probability_mean(q[0].reshape(2, 2, seq, d), k[0],
                                 want_lse[0].reshape(2, 2, seq), sel[0],
                                 scale, 512, 512, 1024)
    np.testing.assert_allclose(p_got, p_want, rtol=1e-3, atol=1e-6)
    np.testing.assert_allclose(jnp.sum(p_want, axis=1), 1.0, rtol=1e-4)
    g_want = jax.grad(loss(lambda *a: sa.selected_attention(
        *a, sel, scale)[0]), (0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)


def _lse(q, k, sel, scale):
    return sa.selected_attention(q, k, k, sel, scale)[1]


def _plain_index_kl(qi, ki, w, q, k, sel, scale):
    """The loss spelled over whole [S, S] arrays, differentiated by jax."""
    b, hq, seq, d = q.shape
    keep = sa.unpack_selection(sel)
    kk = jnp.repeat(k, hq // k.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * scale
    p = jax.lax.stop_gradient(jnp.mean(jax.nn.softmax(
        jnp.where(keep[:, None], s, -jnp.inf), axis=-1), axis=1))
    scores = jnp.stack([sa.index_scores(qi[i], ki[i], w[i])
                        for i in range(b)])
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    safe = jnp.where(keep & (p > 0), p, 1.0)          # 0 log 0 = 0
    return jnp.sum(jnp.where(keep, p * (jnp.log(safe)
                                        - jnp.where(keep, log_q, 0.0)),
                             0.0)) / (b * seq)


def test_index_loss_and_its_own_gradients_equal_jax_autodiff(small_blocks):
    seq = 64
    q, k, v, sel = _attention_operands(seq)
    parts = [_indexer(seq, 7 + i) for i in range(2)]
    qi, ki, w = (jnp.stack([p[j] for p in parts]) for j in range(3))
    scale = 8 ** -0.5
    lse = _lse(q, k, sel, scale)
    got = sa.index_kl_loss(qi, ki, w, q, k, lse, sel, scale)
    want = _plain_index_kl(qi, ki, w, q, k, sel, scale)
    assert float(want) > 1e-3
    np.testing.assert_allclose(got, want, rtol=1e-5)
    g_got = jax.grad(lambda *a: 3.0 * sa.index_kl_loss(*a, lse, sel, scale),
                     (0, 1, 2, 3, 4))(qi, ki, w, q, k)
    g_want = jax.grad(lambda *a: 3.0 * _plain_index_kl(*a, sel, scale),
                      (0, 1, 2))(qi, ki, w, q, k)
    for a, b in zip(g_got[:3], g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # p is a constant: the attention's operands get exactly nothing
    assert not np.asarray(g_got[3]).any() and not np.asarray(g_got[4]).any()


def _ctx():
    return LoweringContext(jax.random.PRNGKey(0))


def test_ops_select_attend_and_lose_through_their_lowerings(small_blocks):
    from paddle_tpu.fluid import trace
    seq = 64
    q, k, v, _ = _attention_operands(seq)
    parts = [_indexer(seq, 11 + i) for i in range(2)]
    qi, ki, w = (jnp.stack([p[j] for p in parts]) for j in range(3))
    before = {n: trace.metrics().counter(n).value for n in (
        "sparse_attention.lowering.xla",
        "sparse_attention.topk_lowering.bisect_xla")}
    index = get_op("sparse_attention_index")
    assert not index.differentiable
    out = index.fn({"QI": [qi], "KI": [ki], "W": [w]}, {"topk": 6}, _ctx())
    sel = out["Selection"][0]
    assert sel.dtype == jnp.uint8 and sel.shape == (2, seq, seq // 8)
    assert float(out["SelectedKeysMean"][0][0]) == pytest.approx(
        (21 + (seq - 6) * 6) / seq)
    attn = get_op("fused_multihead_attention")
    assert "Selection" in attn.nondiff_inputs
    assert "LSE" in attn.nondiff_outputs
    attended = attn.fn({"Q": [q], "K": [k], "V": [v], "Selection": [sel]},
                       {"causal": True, "scale": 0.3}, _ctx())
    np.testing.assert_allclose(
        attended["Out"][0], _gathered_attention(q, k, v, sel, 0.3),
        rtol=2e-5, atol=2e-5)
    loss = get_op("sparse_attention_index_loss")
    assert set(loss.nondiff_inputs) == {"Q", "K", "LSE", "Selection"}
    lo = loss.fn({"QI": [qi], "KI": [ki], "W": [w], "Q": [q], "K": [k],
                  "LSE": attended["LSE"], "Selection": [sel]},
                 {"scale": 0.3, "weight": 2.0}, _ctx())
    np.testing.assert_allclose(
        lo["Loss"][0][0], 2.0 * _plain_index_kl(qi, ki, w, q, k, sel, 0.3),
        rtol=1e-5)
    np.testing.assert_allclose(lo["IndexKL"][0] * 2.0, lo["Loss"][0])
    for name, value in before.items():
        assert trace.metrics().counter(name).value == value + 1
    # an attention without a selection has one output, as it always had
    plain = attn.fn({"Q": [q], "K": [k], "V": [v]}, {"causal": True},
                    _ctx())
    assert set(plain) == {"Out"}


@pytest.mark.parametrize("bad", [{"causal": False}, {"window": 4}])
def test_a_selection_is_causal_and_has_no_window(bad):
    q, k, v, sel = _attention_operands(16, hkv=4)
    with pytest.raises(ValueError, match="selection"):
        get_op("fused_multihead_attention").fn(
            {"Q": [q], "K": [k], "V": [v], "Selection": [sel]},
            {"causal": True, **bad}, _ctx())


@pytest.mark.parametrize("seq,d,hkv,want", [
    (16384, 128, 4, "selected_kernel"), (1024, 128, 32, "selected_kernel"),
    (1024, 192, 4, "xla"),                  # two widths: not this kernel's
    (768, 128, 4, "xla"),                   # not whole 512 x 512 tiles
    (1024, 64, 4, "xla")])                  # a head the lanes do not take
def test_attention_path_answers_a_selection(seq, d, hkv, want):
    from paddle_tpu.ops.attention import attention_path, path_at
    q = jax.ShapeDtypeStruct((1, 32, seq, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, hkv, seq, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, hkv, seq, 128), jnp.bfloat16)
    sel = jax.ShapeDtypeStruct((1, seq, seq // 8), jnp.uint8)
    assert attention_path(q, k, v, None, True, False, 0, sel) == want
    assert path_at(KernelSite(), q, k, v, None, True, False, 0, sel) == want
    assert path_at(None, q, k, v, None, True, False, 0, sel) == "xla"
    # without a selection the same operands keep the answer they had
    assert attention_path(q, k, v, None, True, False) in (
        "splash_kernel", "xla")


def test_rotary_embedding_under_three_unequal_rows_of_positions():
    seq, d, sections = 10, 16, [2, 3, 3]
    r = np.random.RandomState(5)
    x = r.randn(2, 3, seq, d).astype("float32")
    pos = np.stack([np.arange(seq), 3 + np.arange(seq) // 2,
                    7 - np.arange(seq) % 4]).astype("float32")
    inv_freq = [1e4 ** (-2.0 * i / d) for i in range(d // 2)]
    got = get_op("rotary_embedding").fn(
        {"X": [jnp.asarray(x)], "Positions": [jnp.asarray(pos)]},
        {"inv_freq": inv_freq, "sections": sections}, _ctx())["Out"][0]
    row = [0, 0, 1, 1, 1, 2, 2, 2]
    want = np.empty_like(x)
    for t in range(seq):
        for i in range(d // 2):
            a = pos[row[i], t] * inv_freq[i]
            lo, hi = x[..., t, i], x[..., t, i + d // 2]
            want[..., t, i] = lo * np.cos(a) - hi * np.sin(a)
            want[..., t, i + d // 2] = hi * np.cos(a) + lo * np.sin(a)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # three equal rows 0..S-1 are the op without positions
    plain = get_op("rotary_embedding").fn(
        {"X": [jnp.asarray(x)]}, {"inv_freq": inv_freq}, _ctx())["Out"][0]
    text = np.tile(np.arange(seq, dtype="float32"), (3, 1))
    same = get_op("rotary_embedding").fn(
        {"X": [jnp.asarray(x)], "Positions": [jnp.asarray(text)]},
        {"inv_freq": inv_freq, "sections": sections}, _ctx())["Out"][0]
    np.testing.assert_array_equal(plain, same)
    with pytest.raises(ValueError, match="sections"):
        get_op("rotary_embedding").fn(
            {"X": [jnp.asarray(x)], "Positions": [jnp.asarray(pos)]},
            {"inv_freq": inv_freq, "sections": [4, 3, 3]}, _ctx())


def test_moe_route_bounds_the_held_rows_and_fails_the_step_beyond():
    """``max_rows``: the buffer of held assignments is that long; a step
    that routes more to the held experts gets NaN weights."""
    r = np.random.RandomState(9)
    x = jnp.asarray(r.randn(12, 4).astype("float32"))
    router = jnp.asarray(r.randn(4, 8).astype("float32"))
    route = get_op("moe_route").fn
    attrs = {"top_k": 2, "num_held": 2, "first_expert": 0}
    free = route({"X": [x], "RouterWeight": [router]}, attrs, _ctx())
    held = int(free["GroupSizes"][0].sum())
    assert 0 < held < 24 and free["Order"][0].shape == (24,)
    fits = route({"X": [x], "RouterWeight": [router]},
                 {**attrs, "max_rows": held}, _ctx())
    assert fits["Order"][0].shape == (held,)
    np.testing.assert_array_equal(fits["TopKWeight"][0],
                                  free["TopKWeight"][0])
    np.testing.assert_array_equal(fits["Order"][0], free["Order"][0][:held])
    # the bounded layer computes what the unbounded one does
    from paddle_tpu.parallel import moe
    w = [jnp.asarray(r.randn(2, *s).astype("float32") * 0.3)
         for s in ((4, 6), (4, 6), (6, 4))]

    def layer(out, tokens=x):
        plan = moe.Plan(out["Order"][0], out["Pos"][0], out["GroupSizes"][0])
        ys = moe.held_ffn(moe.dispatch(tokens, plan), plan.group_sizes, *w)
        return moe.combine(ys, out["TopKWeight"][0], plan)

    def grad(out):
        return jax.grad(lambda t: jnp.sum(jnp.sin(layer(out, t))))(x)
    np.testing.assert_allclose(layer(fits), layer(free), rtol=1e-6)
    np.testing.assert_allclose(grad(fits), grad(free), rtol=1e-5, atol=1e-6)
    over = route({"X": [x], "RouterWeight": [router]},
                 {**attrs, "max_rows": held - 1}, _ctx())
    assert np.isnan(np.asarray(over["TopKWeight"][0])).all()


def test_linear_cross_entropy_equals_the_head_and_its_loss(monkeypatch):
    """``fc`` + ``softmax_with_cross_entropy`` in blocks of tokens: the same
    loss and the same gradients, several blocks."""
    from paddle_tpu.ops import decoder_ops
    monkeypatch.setattr(decoder_ops, "_HEAD_ROWS", 8)
    r = np.random.RandomState(4)
    x = jnp.asarray(r.randn(2, 12, 16).astype("float32"))
    w = jnp.asarray(r.randn(16, 40).astype("float32") * 0.3)
    labels = jnp.asarray(r.randint(0, 40, (2, 12, 1)))
    weights = jnp.asarray(r.rand(2, 12, 1).astype("float32"))

    def plain(x, w):
        logp = jax.nn.log_softmax(x @ w, axis=-1)
        return jnp.sum(-jnp.take_along_axis(logp, labels, axis=-1) * weights)

    def op(x, w):
        out = get_op("linear_cross_entropy").fn(
            {"X": [x], "W": [w], "Label": [labels]}, {}, _ctx())["Loss"][0]
        assert out.shape == (2, 12, 1) and out.dtype == jnp.float32
        return jnp.sum(out * weights)
    np.testing.assert_allclose(op(x, w), plain(x, w), rtol=1e-5)
    for a, b in zip(jax.grad(op, (0, 1))(x, w), jax.grad(plain, (0, 1))(x, w)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_loss_behind_the_kernel_differentiates_in_one_function():
    """Attention and the indexer's loss behind it in one differentiated
    function, the kernels in the interpreter: no gradient passes through the
    log-sum-exp that joins them (a cotangent for it did not get through the
    kernel wrapper's ``lax.map``: ``chip_smoke.py`` found it on the chip)."""
    from paddle_tpu.ops import pallas_kernels as pk
    seq, d = 1024, 128
    r = np.random.RandomState(6)
    q = jnp.asarray(r.randn(1, 2, seq, d).astype("float32"))
    k = jnp.asarray(r.randn(1, 1, seq, d).astype("float32"))
    qi, ki, w = (a[None] for a in _indexer(seq, 8))
    sel = sa.select_topk(qi[0], ki[0], w[0], 64)[None]

    def both(attend):
        def f(q, k, qi, ki, w):
            out, lse = attend(q, k, k, sel, d ** -0.5)
            return jnp.sum(jnp.sin(out)) + sa.index_kl_loss(
                qi, ki, w, q, k, lse, sel, d ** -0.5)
        return jax.grad(f, (0, 1, 2, 3, 4))(q, k, qi, ki, w)
    with pltpu.force_tpu_interpret_mode():
        got = both(pk.selected_attention_tpu)
    for a, b in zip(got, both(sa.selected_attention)):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)


def _loss_operands(seq, hi=16, di=64, sharp=1.0, topk=64, seed=3):
    """Two sequences at the kernels' widths: 4 : 2 heads of 128, ``hi``
    index heads of ``di``, a selection of ``topk`` keys and the attention's
    own log-sum-exp; ``sharp`` scales the queries (at 25 most probabilities
    of a set underflow to exactly 0)."""
    r = np.random.RandomState(seed)
    q = jnp.asarray((sharp * r.randn(2, 4, seq, 128)).astype("float32"))
    k = jnp.asarray(r.randn(2, 2, seq, 128).astype("float32"))
    qi = jnp.asarray(r.randn(2, hi, seq, di).astype("float32"))
    ki = jnp.asarray(r.randn(2, seq, di).astype("float32"))
    w = jnp.asarray(r.randn(2, seq, hi).astype("float32"))
    sel = jnp.stack([sa.select_topk(qi[i], ki[i], w[i], topk)
                     for i in range(2)])
    scale = 128 ** -0.5
    return qi, ki, w, q, k, _lse(q, k, sel, scale), sel, scale


@pytest.mark.parametrize("case", ["one_super_block", "two_super_blocks",
                                  "zero_probabilities_in_the_set"])
def test_loss_kernels_in_interpret_mode_equal_the_jnp_path_and_autodiff(
        case, monkeypatch):
    """The two passes of ``pallas_kernels.index_kl_tpu`` (row statistics
    and the loss; the gradients of QI, KI, W) against ``_index_kl_one``'s
    ``jnp`` path and against jax's autodiff of the plain spelling: 1024
    tokens (two tiles a side), 16 x 64 index heads, 64 keys kept, two
    sequences; rows before the 64th have fewer keys than ``topk``; with a
    second super block dKI sums over calls; with sharp attention the set
    holds pairs whose probability is exactly 0 (the ``held`` branch)."""
    from paddle_tpu.ops import pallas_kernels as pk
    seq = 1024
    if case == "two_super_blocks":
        monkeypatch.setattr(sa, "_SUPER_ROWS", 512)
    sharp = 25.0 if case == "zero_probabilities_in_the_set" else 1.0
    qi, ki, w, q, k, lse, sel, scale = _loss_operands(seq, sharp=sharp)
    assert sa._block_rows(seq)[0] == (512 if case == "two_super_blocks"
                                      else 1024)
    assert pk.index_loss_supported(qi, ki, w, q, k, sel,
                                   sa._block_rows(seq)[0])
    keep = np.asarray(sa.unpack_selection(sel))
    assert keep[0, 0].sum() == 1 and keep[0, 40].sum() == 41 \
        and keep[0, -1].sum() == 64
    p = np.asarray(sa.probability_mean(
        q[0].reshape(2, 2, seq, 128), k[0], lse[0].reshape(2, 2, seq),
        sel[0], scale, 0, seq, seq))
    assert ((p == 0) & keep[0]).any() == (sharp > 1)

    def both(fn):
        return jax.value_and_grad(
            lambda *a: 3.0 * fn(*a), (0, 1, 2, 3, 4, 5))(qi, ki, w, q, k,
                                                          lse)
    with pltpu.force_tpu_interpret_mode():
        got, g_got = both(lambda *a: sa.index_kl_loss(*a, sel, scale, True))
        alone = sa.index_kl_loss(qi, ki, w, q, k, lse, sel, scale, True)
    want, g_want = both(lambda *a: sa.index_kl_loss(*a, sel, scale, False))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(3.0 * alone, want, rtol=1e-5)
    plain, g_plain = jax.value_and_grad(
        lambda *a: 3.0 * _plain_index_kl(*a, q, k, sel, scale),
        (0, 1, 2))(qi, ki, w)
    np.testing.assert_allclose(got, plain, rtol=1e-5)
    for name, a, b, c in zip(("dQI", "dKI", "dW"), g_got, g_want, g_plain):
        top = float(jnp.abs(c).max())
        assert top > 0, name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * top,
                                   err_msg=name)
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-5 * top,
                                   err_msg=name)
    # p is a constant: Q, K and LSE get exactly nothing
    for g in g_got[3:]:
        assert not np.asarray(g).any()


class _Chip(LoweringContext):
    """A context that answers as on a TPU (the kernels then run in the
    interpreter)."""

    def pallas_ok(self):
        return not self.partitioned


@pytest.mark.parametrize("case,path", [
    ("chip", "kernel"), ("cpu", "xla"), ("partitioned", "xla"),
    ("index_heads_of_48", "xla"), ("not_whole_tiles", "xla")])
def test_the_loss_op_counts_which_lowering_it_took(case, path):
    """``sparse_attention.loss_lowering.kernel`` once per lowering where the
    context allows a kernel and ``index_loss_supported`` covers the shapes,
    ``.xla`` elsewhere; both give the plain spelling's loss."""
    from paddle_tpu.fluid import trace
    seq = 768 if case == "not_whole_tiles" else 512
    di = 48 if case == "index_heads_of_48" else 64
    qi, ki, w, q, k, lse, sel, scale = _loss_operands(seq, di=di, topk=32)
    ctx = (LoweringContext if case == "cpu" else _Chip)(
        jax.random.PRNGKey(0))
    ctx.partitioned = case == "partitioned"
    names = [f"sparse_attention.loss_lowering.{n}"
             for n in ("kernel", "xla", "pallas")]
    before = [trace.metrics().counter(n).value for n in names]
    with pltpu.force_tpu_interpret_mode():
        out = get_op("sparse_attention_index_loss").fn(
            {"QI": [qi], "KI": [ki], "W": [w], "Q": [q], "K": [k],
             "LSE": [lse], "Selection": [sel]}, {"scale": scale}, ctx)
    after = [trace.metrics().counter(n).value for n in names]
    assert [a - b for a, b in zip(after, before)] \
        == [path == "kernel", path == "xla", 0]
    np.testing.assert_allclose(
        out["Loss"][0][0], _plain_index_kl(qi, ki, w, q, k, sel, scale),
        rtol=1e-5)


def _whole_number_indexer(seq, case, hi=8, di=64, batch=2, seed=5):
    """Index operands whose every score is exact in float32 whatever the
    order of its sums, so the kernel and ``select_topk`` must set the same
    bits: QI, KI bfloat16 whole numbers, W float32 whole numbers.

    ``small``: entries in -2..2 (W -3..3), many equal scores a row.
    ``distinct``: a key's two first numbers spell a permutation of the
    keys, every head of a query scales it by 1 or 2, and its signed head
    weights by their sum: each row's scores are distinct multiples of that
    permutation (never 0), negative where the sum is.  ``planted``: as
    ``distinct``, and every 37th query's QI is 0, so all its scores are
    equal: every head's term -0.0 where its weights are all negative (every
    74th), else 0.0."""
    r = np.random.RandomState(seed)
    if case == "small":
        qi = r.randint(-2, 3, (batch, hi, seq, di))
        ki = r.randint(-2, 3, (batch, seq, di))
        w = r.randint(-3, 4, (batch, seq, hi))
    else:
        perm = np.stack([r.permutation(seq) for _ in range(batch)])
        ki = np.zeros((batch, seq, di))
        ki[..., 0], ki[..., 1] = perm // 32, perm % 32
        c = r.randint(1, 3, (batch, hi, seq))
        qi = np.zeros((batch, hi, seq, di))
        qi[..., 0], qi[..., 1] = 32 * c, c
        w = r.randint(-4, 5, (batch, seq, hi))
        flat = np.einsum("bth,bht->bt", w, c) == 0      # no zero multiple
        w[..., 0] += flat * np.where(w[..., 0] == -1, 2, 1)
        if case == "planted":
            qi[:, :, ::37] = 0
            w[:, ::74] = -1 - np.abs(w[:, ::74])         # -0.0 terms
    return (jnp.asarray(qi, jnp.bfloat16), jnp.asarray(ki, jnp.bfloat16),
            jnp.asarray(w, jnp.float32))


@pytest.mark.parametrize("case,topk,super_rows", [
    ("small", 64, 2048),
    ("small", 200, 2048),          # rows before the 200th keep all theirs
    ("small", 64, 512),            # select_topk in two super blocks
    ("distinct", 64, 2048),
    ("distinct", 700, 512),
    ("planted", 96, 512)])
def test_selection_kernel_in_interpret_mode_sets_select_topks_bits(
        case, topk, super_rows, monkeypatch):
    """``pallas_kernels.select_topk_tpu`` (scores, the 32 counting passes,
    the ties in key order, the packing) against ``select_topk`` bit for bit
    and against a stable sort of the scores: 1024 tokens (two key tiles,
    eight blocks of queries), two sequences."""
    from paddle_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(sa, "_SUPER_ROWS", super_rows)
    seq = 1024
    qi, ki, w = _whole_number_indexer(seq, case)
    assert pk.index_select_supported(qi, ki, w)
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(pk.select_topk_tpu(qi, ki, w, topk))
    assert got.dtype == np.uint8 and got.shape == (2, seq, seq // 8)
    for i in range(2):
        np.testing.assert_array_equal(
            got[i], np.asarray(sa.select_topk(qi[i], ki[i], w[i], topk)))
        scores = np.asarray(sa.index_scores(qi[i], ki[i], w[i]))
        want = _oracle_selection(scores, topk)
        np.testing.assert_array_equal(
            np.asarray(sa.unpack_selection(jnp.asarray(got[i]))), want)
        # rows where more keys equal the threshold than are still needed
        tied = [t for t in range(topk, seq)
                if (scores[t, :t + 1] >= np.sort(scores[t, :t + 1])[-topk]
                    ).sum() > topk]
        if case == "small":
            assert len(tied) > seq // 4
        else:
            assert tied == [t for t in range(topk, seq)
                            if case == "planted" and t % 37 == 0]
        assert (scores < 0).any(axis=1).sum() > seq // 4
        if case == "planted":
            # relu(0) times a negative weight is -0.0 a head: the kernel's
            # sum, which starts from the first head's term, is -0.0 there,
            # the jnp sum, which starts from +0.0, is 0.0
            assert (scores[::37] == 0).all()
            assert (np.asarray(w)[i, ::74] < 0).all()
            # every key ties: the first k in key order
            np.testing.assert_array_equal(
                want[::37], np.tril(np.ones((seq, seq), np.int8))[::37]
                * (np.arange(seq) < topk)[None, :])


@pytest.mark.parametrize("case,path", [
    ("chip", "kernel"), ("cpu", "bisect_xla"), ("partitioned", "bisect_xla"),
    ("index_heads_of_48", "bisect_xla"), ("not_whole_tiles", "bisect_xla"),
    ("longer_than_vmem", "bisect_xla")])
def test_the_index_op_counts_which_lowering_it_took(case, path):
    """``sparse_attention.topk_lowering.kernel`` once per lowering where the
    context allows a kernel and ``index_select_supported`` covers the
    shapes (DI whole half lane groups, S whole 512-key tiles, a grid step
    within the VMEM of a call inside a loop), ``.bisect_xla`` elsewhere;
    both set ``select_topk``'s bits."""
    from paddle_tpu.fluid import trace
    from paddle_tpu.ops import pallas_kernels as pk
    names = [f"sparse_attention.topk_lowering.{n}"
             for n in ("kernel", "bisect_xla")]
    if case == "longer_than_vmem":
        # whole 512-key tiles, but 27.5 MiB of VMEM a grid step: traced
        # only, the XLA row blocks at 32768 keys are too slow for a test
        seq = 32768
        ops = {"QI": [jax.ShapeDtypeStruct((1, 16, seq, 64), jnp.bfloat16)],
               "KI": [jax.ShapeDtypeStruct((1, seq, 64), jnp.bfloat16)],
               "W": [jax.ShapeDtypeStruct((1, seq, 16), jnp.float32)]}
        assert not pk.index_select_supported(
            *(ops[n][0] for n in ("QI", "KI", "W")))
        before = [trace.metrics().counter(n).value for n in names]
        out = jax.eval_shape(
            lambda o: get_op("sparse_attention_index").fn(
                o, {"topk": 2048}, _Chip(jax.random.PRNGKey(0))), ops)
        after = [trace.metrics().counter(n).value for n in names]
        assert [a - b for a, b in zip(after, before)] == [0, 1]
        assert out["Selection"][0].shape == (1, seq, seq // 8)
        return
    seq = 768 if case == "not_whole_tiles" else 512
    qi, ki, w = _whole_number_indexer(
        seq, "small", di=48 if case == "index_heads_of_48" else 64)
    ctx = (LoweringContext if case == "cpu" else _Chip)(
        jax.random.PRNGKey(0))
    ctx.partitioned = case == "partitioned"
    before = [trace.metrics().counter(n).value for n in names]
    with pltpu.force_tpu_interpret_mode():
        out = get_op("sparse_attention_index").fn(
            {"QI": [qi], "KI": [ki], "W": [w]}, {"topk": 32}, ctx)
    after = [trace.metrics().counter(n).value for n in names]
    assert [a - b for a, b in zip(after, before)] \
        == [path == "kernel", path == "bisect_xla"]
    for i in range(2):
        np.testing.assert_array_equal(
            out["Selection"][0][i], sa.select_topk(qi[i], ki[i], w[i], 32))
    assert float(out["SelectedKeysMean"][0][0]) == pytest.approx(
        (32 * 33 / 2 + (seq - 32) * 32) / seq)
