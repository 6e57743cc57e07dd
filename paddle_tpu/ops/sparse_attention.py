"""Attention over a key set that a learned scorer chooses per query
(DeepSeek sparse attention: a "lightning indexer" and an exact top-k).

Three Program ops, one layer of a decoder (docs/sparse_attention.md):

* ``sparse_attention_index`` — the indexer.  ``QI`` [B, HI, S, DI] index
  queries, ``KI`` [B, S, DI] one index key a token, ``W`` [B, S, HI] the
  per-token head weights -> ``Selection`` [B, S, S / 8] uint8, a bit a
  (query, key) pair (bit ``s % 8`` of byte ``s // 8`` of row ``t``), set
  where key ``s`` is one of the ``min(t + 1, topk)`` keys ``s <= t`` with
  the largest
  ``I[t, s] = sum_j W[t, j] relu(QI[t, j] . KI[s])``, ties to the smaller
  ``s``.  Exact: the ``topk``-th largest score of a row is found by a search
  over the float's ordered bits (32 counting passes), not by an approximate
  ``approx_max_k`` and not by a sort.  No gradient: the set is a piecewise
  constant function of the scores.
* ``fused_multihead_attention`` with the optional input ``Selection``
  (ops/attention.py): softmax over the chosen keys only.
* ``sparse_attention_index_loss`` — what trains the indexer: the mean over
  queries of ``KL(p[t, .] || softmax over the set of I[t, .])`` with ``p``
  the attention probabilities of all heads averaged, a constant here (``Q``,
  ``K`` and the attention's log-sum-exp ``LSE`` get no gradient).  It forms
  the probabilities again as ``exp(q . k * scale - LSE)`` (one matmul pass
  over the causal pairs, no softmax: the attention op hands its log-sum-exp
  over as a second output), and, because the loss's own gradient needs
  nothing from downstream but a scalar, it computes the gradients of ``QI``,
  ``KI`` and ``W`` in that same pass and keeps them for the grad op: no
  second pass over the pairs, and nothing of size [S, S] is kept but the
  selection.

Everything here is row blocks of queries against the keys up to the block's
end: no [heads, S, S] array and no float [S, S] array exists; the selection
that waits from a layer's forward for its backward is one bit a pair (32
MiB at 16384 tokens; a byte a pair was 1 GiB over four layers).  The ``jnp`` spellings serve the CPU, partitioned
programs and the selection's index scores on the chip; the selected
attention on the chip is ``pallas_kernels.selected_attention_tpu``
(``attention_path``'s fifth answer) and the whole loss
``pallas_kernels.index_kl_tpu`` (the heads' mean probabilities by
``selected_probability_mean_tpu``, then two passes that keep a tile's
[heads, rows, keys] index scores on the core).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op

# query rows whose blocks share one key extent (the keys up to the super
# block's end: a twelfth more pairs than the causal half at 16384), and the
# query rows of one block: [32 heads, 128, 16384] float32 scores are 256 MiB
_SUPER_ROWS = 2048
_ROWS = 128
_INT_MIN = -2 ** 31


def _block_rows(seq):
    """(super block, block) rows for ``seq`` queries: the module's sizes
    where they divide, else one super block and the largest divisor."""
    sup = _SUPER_ROWS if seq % _SUPER_ROWS == 0 else seq
    rows = min(_ROWS, sup)
    while sup % rows:
        rows -= 1
    return sup, rows


def _scan_row_blocks(body, carry, seq, per_super=None):
    """``body(carry, extent, row0) -> (carry, out)`` over the row blocks of
    ``seq`` queries, ``extent`` (static) the keys a block sees and ``row0``
    (traced) its first row; ``out`` has the block's rows leading.  Returns
    the carry and the outs with all ``seq`` rows leading.  With
    ``per_super(r0, rows, extent)`` (all static) the body is called as
    ``body(carry, extent, row0, r0, that value)``: what is worth computing
    once for a super block's rows."""
    sup, rows = _block_rows(seq)
    outs = []
    for r0 in range(0, seq, sup):
        extent = r0 + sup
        extra = () if per_super is None else (r0, per_super(r0, sup, extent))
        carry, out = lax.scan(
            lambda c, row0, extent=extent, extra=extra: body(
                c, extent, row0, *extra), carry,
            r0 + rows * jnp.arange(sup // rows))
        outs.append(jax.tree_util.tree_map(
            lambda o: o.reshape((sup,) + o.shape[2:]), out))
    return carry, jax.tree_util.tree_map(
        lambda *o: jnp.concatenate(o, axis=0), *outs)


def pack_selection(chosen):
    """[..., N] bool -> [..., N / 8] uint8, key ``s`` in bit ``s % 8`` of
    byte ``s // 8``."""
    if chosen.shape[-1] % 8:
        raise ValueError(f"a selection packs whole bytes of keys: "
                         f"{chosen.shape[-1]} keys")
    bits = chosen.reshape(chosen.shape[:-1] + (-1, 8)).astype(jnp.uint8)
    return jnp.sum(bits << jnp.arange(8, dtype=jnp.uint8), axis=-1,
                   dtype=jnp.uint8)


def unpack_selection(sel):
    """[..., N / 8] uint8 -> [..., N] bool."""
    bits = (sel[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    return bits.reshape(sel.shape[:-1] + (-1,)) != 0


def _rows_of(x, row0, rows, axis):
    return lax.dynamic_slice_in_dim(x, row0, rows, axis=axis)


def _per_sequence(fn, *batched):
    """``fn`` of one sequence's arrays over the leading batch axis, one
    sequence after the other and traced once (``lax.map``: a Python loop
    would trace ``fn`` once per row of the batch, and a program's declared
    batch stands in as a large number while its shapes are inferred)."""
    return lax.map(lambda args: fn(*args), batched)


def index_scores(qi, ki, w):
    """``qi`` [HI, R, DI], ``ki`` [N, DI], ``w`` [R, HI] -> [R, N] float32:
    ``sum_j w[r, j] relu(qi[j, r] . ki[n])``, the products accumulated in
    float32 whatever the operands are."""
    z = jnp.einsum("hrd,nd->hrn", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(z) * w.astype(jnp.float32).T[:, :, None],
                   axis=0)


def ordered_keys(x):
    """float32 -> int32 whose signed order is the floats' order (-0.0 is
    0.0)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x)
                                    .astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def kth_largest(keys, k):
    """``keys`` [R, N] int32, ``k`` [R] (1 <= k <= N) -> [R] the k-th
    largest key of each row, exactly: the answer is built from its top bit
    down (in the unsigned order ``key ^ int_min``), a bit staying set where
    at least ``k`` keys reach the candidate.  32 passes that count."""
    sign = jnp.int32(_INT_MIN)

    def bit(i, found):
        cand = found | lax.shift_left(jnp.int32(1), jnp.int32(31) - i)
        reach = jnp.sum(keys >= (cand ^ sign)[:, None], axis=1,
                        dtype=jnp.int32)
        return jnp.where(reach >= k, cand, found)

    found = lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[0], jnp.int32))
    return found ^ sign


def select_rows(scores, row0, topk):
    """The selection of one block: ``scores`` [R, N] float32 of the queries
    ``row0 ..`` against the keys ``0 .. N`` -> [R, N] bool, the ``min(t + 1,
    topk)`` largest of each query's keys ``s <= t``, ties to the smaller
    ``s``."""
    r, n = scores.shape
    t = row0 + jnp.arange(r, dtype=jnp.int32)
    causal = jnp.arange(n, dtype=jnp.int32)[None, :] <= t[:, None]
    keys = jnp.where(causal, ordered_keys(scores), jnp.int32(_INT_MIN))
    k = jnp.minimum(t + 1, jnp.int32(topk))
    tau = kth_largest(keys, k)[:, None]
    above = keys > tau
    equal = (keys == tau) & causal
    need = (k - jnp.sum(above, axis=1, dtype=jnp.int32))[:, None]
    # nearly always the equal keys are exactly the ones still needed (one:
    # the threshold itself); the order among them matters only otherwise
    return lax.cond(
        jnp.any(jnp.sum(equal, axis=1, dtype=jnp.int32)[:, None] > need),
        lambda: above | (equal & (jnp.cumsum(equal.astype(jnp.int32),
                                             axis=1) <= need)),
        lambda: above | equal)


def select_topk(qi, ki, w, topk):
    """One sequence's selection: ``qi`` [HI, S, DI], ``ki`` [S, DI], ``w``
    [S, HI] -> [S, S / 8] uint8 (``pack_selection``)."""
    seq = ki.shape[0]
    _, rows = _block_rows(seq)

    def block(carry, extent, row0):
        scores = index_scores(_rows_of(qi, row0, rows, 1), ki[:extent],
                              _rows_of(w, row0, rows, 0))
        chosen = select_rows(scores, row0, topk)
        return carry, pack_selection(
            jnp.pad(chosen, ((0, 0), (0, seq - extent))))

    return _scan_row_blocks(block, (), seq)[1]


def selection_gauges(sel):
    """(mean selected keys a query, share of the causal 512 x 512 tiles that
    hold a selected pair) of ``sel`` [B, S, S / 8]; the tile is the
    attention kernels' block (the whole sequence where it is shorter)."""
    b, seq, _ = sel.shape
    chosen = unpack_selection(sel)
    mean = jnp.sum(chosen, dtype=jnp.int32).astype(jnp.float32) / (b * seq)
    tile = min(512, seq)
    if seq % tile:
        return mean, jnp.float32(0.0)
    n = seq // tile
    held = jnp.any(chosen.reshape(b, n, tile, n, tile), axis=(2, 4))
    return mean, jnp.sum(held, dtype=jnp.float32) / (b * n * (n + 1) // 2)


def selected_attention(q, k, v, sel, scale):
    """The plain ``jnp`` selected attention: ``q`` [B, Hq, S, D], ``k``
    [B, Hkv, S, D], ``v`` [B, Hkv, S, Dv], ``sel`` [B, S, S / 8] -> ([B, Hq,
    S, Dv], the log-sum-exp of every query's scores over its set [B, Hq, S]
    float32, which carries no gradient): softmax over each query's chosen
    keys.  Blocks of queries against the keys up to the block's end, each
    recomputed in backward."""
    _, hq, seq, d = q.shape
    hkv = k.shape[1]
    _, rows = _block_rows(seq)
    neg = jnp.finfo(jnp.float32).min

    def one(q1, k1, v1, sel1):
        qg = q1.reshape(hkv, hq // hkv, seq, d)

        @jax.checkpoint
        def attend(qb, kb, vb, keep):
            s = jnp.where(keep, jnp.einsum(
                "hgrd,hkd->hgrk", qb, kb,
                preferred_element_type=jnp.float32) * scale, neg)
            lse = lax.stop_gradient(jax.nn.logsumexp(s, axis=-1))
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("hgrk,hkd->hgrd", p.astype(qb.dtype), vb), lse

        def block(carry, extent, row0):
            keep = unpack_selection(
                _rows_of(sel1, row0, rows, 0))[:, :extent]
            out, lse = attend(_rows_of(qg, row0, rows, 2), k1[:, :extent],
                              v1[:, :extent], keep)
            return carry, (out.transpose(2, 0, 1, 3), lse.transpose(2, 0, 1))

        out, lse = _scan_row_blocks(block, (), seq)[1]  # [S, hkv, g, ..]
        return (out.transpose(1, 2, 0, 3).reshape(hq, seq, v1.shape[-1]),
                lse.transpose(1, 2, 0).reshape(hq, seq))

    return _per_sequence(one, q, k, v, sel)


def probability_mean(qg, k, lse, sel, scale, r0, rows, extent):
    """The heads' mean attention probability of the queries ``r0 .. r0 +
    rows`` for the keys ``0 .. extent``, [rows, extent] float32: ``exp(q . k
    * scale - lse)`` where the pair is selected, 0 elsewhere.  ``qg`` [Hkv,
    G, S, D], ``k`` [Hkv, S, D], ``lse`` [Hkv, G, S], ``sel`` [S, S / 8]."""
    keep = unpack_selection(sel[r0:r0 + rows])[:, :extent]
    s = jnp.einsum("hgrd,hkd->hgrk", qg[:, :, r0:r0 + rows], k[:, :extent],
                   preferred_element_type=jnp.float32) * scale
    p = jnp.exp(jnp.where(keep, s, jnp.finfo(jnp.float32).min)
                - lse[:, :, r0:r0 + rows, None])
    return jnp.mean(p, axis=(0, 1))


def _index_kl_one(qi, ki, w, q, k, lse, sel, scale, with_grads, on_chip):
    """One sequence: (sum over queries of KL(p || softmax_set(I)), and with
    ``with_grads`` the gradients of that sum with respect to ``qi``, ``ki``
    and ``w``).  ``qi`` [HI, S, DI], ``ki`` [S, DI], ``w`` [S, HI], ``q``
    [Hq, S, D], ``k`` [Hkv, S, D], ``lse`` [Hq, S], ``sel`` [S, S / 8].
    ``on_chip``: all of it by ``pallas_kernels.index_kl_tpu``.  Else the
    probabilities are formed a super block of queries at a time, the index
    scores and what follows them a block at a time."""
    hq, seq, d = q.shape
    hkv = k.shape[0]
    sup, rows = _block_rows(seq)
    qg = q.reshape(hkv, hq // hkv, seq, d)
    lse_g = lse.reshape(hkv, hq // hkv, seq)
    if on_chip:
        from .pallas_kernels import index_kl_tpu
        return index_kl_tpu(qi, ki, w, qg, k, lse_g, sel, scale, sup,
                            with_grads)
    neg = jnp.finfo(jnp.float32).min

    def block(dki, extent, row0, r0, p_super):
        keep = unpack_selection(_rows_of(sel, row0, rows, 0))[:, :extent]
        p = _rows_of(p_super, row0 - r0, rows, 0)               # [R, N]
        args = (_rows_of(qi, row0, rows, 1), ki[:extent],
                _rows_of(w, row0, rows, 0))
        if with_grads:
            scores, pull = jax.vjp(index_scores, *args)
        else:
            scores = index_scores(*args)
        log_q = jax.nn.log_softmax(jnp.where(keep, scores, neg), axis=-1)
        held = keep & (p > 0)
        kl = jnp.sum(jnp.where(held, p * (jnp.log(jnp.where(held, p, 1.0))
                                          - log_q), 0.0), axis=-1)
        if not with_grads:
            return dki, (kl,)
        # d KL / d I = softmax_set(I) sum(p) - p (the sum is 1 to rounding
        # under the attention's own log-sum-exp)
        dqi, dki_block, dw = pull(jnp.where(
            keep, jnp.exp(log_q) * jnp.sum(p, axis=-1, keepdims=True) - p,
            0.0))
        dki = dki.at[:extent].add(dki_block.astype(jnp.float32))
        return dki, (kl, dqi.transpose(1, 0, 2), dw)

    dki, out = _scan_row_blocks(
        block, jnp.zeros(ki.shape, jnp.float32), seq,
        lambda r0, sup, extent: probability_mean(qg, k, lse_g, sel, scale,
                                                 r0, sup, extent))
    if not with_grads:
        return jnp.sum(out[0]), None
    kl, dqi, dw = out
    return jnp.sum(kl), (dqi.transpose(1, 0, 2), dki.astype(ki.dtype), dw)


def _index_kl(qi, ki, w, q, k, lse, sel, scale, on_chip, with_grads):
    pairs = ki.shape[0] * ki.shape[1]
    loss, grads = _per_sequence(
        lambda *one: _index_kl_one(*one, scale, with_grads, on_chip),
        qi, ki, w, q, k, lse, sel)
    if not with_grads:
        return jnp.sum(loss) / pairs, None
    return jnp.sum(loss) / pairs, tuple(g / pairs for g in grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def index_kl_loss(qi, ki, w, q, k, lse, sel, scale, on_chip=False):
    """Mean over the batch's queries of ``KL(p[t, .] || softmax over the
    set of I[t, .])``, ``p`` the mean over the heads of the selected
    attention's probabilities ``exp(q . k * scale - lse)`` (``q``, ``k`` and
    the log-sum-exp ``lse`` [B, Hq, S] as the attention op has them), which
    is a constant: the gradient reaches ``qi``, ``ki`` and ``w`` only, and is
    computed with the loss.  ``on_chip``: the whole loss by the Pallas
    kernels."""
    return _index_kl(qi, ki, w, q, k, lse, sel, scale, on_chip, False)[0]


def _index_kl_fwd(qi, ki, w, q, k, lse, sel, scale, on_chip):
    return _index_kl(qi, ki, w, q, k, lse, sel, scale, on_chip, True)


def _index_kl_bwd(scale, on_chip, grads, g):
    g = g.astype(jnp.float32)
    return tuple((g * d).astype(d.dtype) for d in grads) + (None,) * 4


index_kl_loss.defvjp(_index_kl_fwd, _index_kl_bwd)


def _count(kind, path):
    from ..fluid import trace
    trace.metrics().counter(f"sparse_attention.{kind}.{path}").inc()


@register_op("sparse_attention_index", differentiable=False)
def _sparse_attention_index(ins, attrs, ctx):
    """QI [B, HI, S, DI], KI [B, S, DI], W [B, S, HI] -> Selection [B, S,
    S / 8] uint8 (the module's docstring).  SelectedKeysMean [1] and TileOccupancy
    [1] stay on the device and are read by the host when a runner drains.
    ``sparse_attention.topk_lowering.bisect_xla`` counts the lowerings: the
    threshold search has one spelling (``kth_largest``; docs/
    sparse_attention.md has what a kernel of its own would and would not
    buy)."""
    qi, ki, w = ins["QI"][0], ins["KI"][0], ins["W"][0]
    _count("topk_lowering", "bisect_xla")
    sel = _per_sequence(
        lambda *one: select_topk(*one, int(attrs["topk"])), qi, ki, w)
    mean, tiles = selection_gauges(sel)
    return {"Selection": [sel], "SelectedKeysMean": [mean.reshape(1)],
            "TileOccupancy": [tiles.reshape(1)]}


@register_op("sparse_attention_index_loss",
             nondiff_inputs=("Q", "K", "LSE", "Selection"),
             nondiff_outputs=("IndexKL",))
def _sparse_attention_index_loss(ins, attrs, ctx):
    """QI, KI, W as the indexer's; Q [B, Hq, S, D], K [B, Hkv, S, D] and LSE
    [B, Hq, S] as the attention op has them (its inputs and its second
    output); Selection [B, S, S / 8] -> Loss [1] float32 = ``weight`` x
    ``index_kl_loss``.  IndexKL [1] (the unweighted loss) stays on the
    device for the host's gauge.  On a chip, outside a partitioned program
    and over shapes the kernels cover
    (``pallas_kernels.index_loss_supported``), the whole loss and its
    gradients come from ``pallas_kernels.index_kl_tpu``: no array with a
    head axis and a key axis reaches HBM
    (``sparse_attention.loss_lowering.<kernel|xla>`` counts which)."""
    q, k, sel = ins["Q"][0], ins["K"][0], ins["Selection"][0]
    on_chip = False
    if ctx.pallas_ok():
        from .pallas_kernels import index_loss_supported
        on_chip = index_loss_supported(
            ins["QI"][0], ins["KI"][0], ins["W"][0], q, k, sel,
            _block_rows(q.shape[2])[0])
    _count("loss_lowering", "kernel" if on_chip else "xla")
    loss = index_kl_loss(ins["QI"][0], ins["KI"][0], ins["W"][0], q, k,
                         ins["LSE"][0], sel, float(attrs["scale"]), on_chip)
    return {"Loss": [(float(attrs.get("weight", 1.0)) * loss).reshape(1)],
            "IndexKL": [lax.stop_gradient(loss).reshape(1)]}
