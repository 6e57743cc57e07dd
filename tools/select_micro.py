"""Time the indexer's selection (``sparse_attention_index``) at one layer
of a shape: the whole XLA op (``sparse_attention.select_topk``) and the
Pallas kernel (``pallas_kernels.select_topk_tpu``), and two pieces of an
XLA spelling of the op's row blocks that is this tool's own (2048-query
super blocks of 128-query blocks, from the public ``index_scores``,
``select_rows`` and ``pack_selection``): its index scores alone, written
out, and its threshold search and packing alone on those scores.  They are
split at HBM, where the op's fusions are not, so their sum is more than
the op's time: they bound the op's pieces, they are not them.

Each piece is mapped over several stacked inputs in one jitted call
(``lax.map``), so the host's round trip of a call (~1 ms) is spread thin;
a plain pass over the same inputs in the same loop gives the floor.  Two
warm calls, then the median of the timed calls, per input.  The kernel's
bits are checked against the XLA op's on exact-integer operands (every sum
is exact whatever its order, so they must be equal); on normal operands the
bytes that differ are counted (the scores may differ in their last bits).

Usage:
  python3 tools/select_micro.py [--seq S] [--out FILE]   # on a TPU: 1 x 16
                                                         # x 16384 x 64
  JAX_PLATFORMS=cpu python3 tools/select_micro.py --tiny   # control flow only
One JSON line per piece on stdout (ms a layer); ``--out`` writes them all.
A CPU run times XLA's CPU backend and the Pallas interpreter: no speed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops import sparse_attention as sa

FULL = {"heads": 16, "seq": 16384, "dim": 64, "topk": 2048, "inputs": 3}
TINY = {"heads": 8, "seq": 1024, "dim": 64, "topk": 64, "inputs": 2}


def operands(n, shape, seed, integer):
    """``n`` stacked inputs of one sequence: QI [n, 1, HI, S, DI] and KI
    [n, 1, S, DI] bfloat16, W [n, 1, S, HI] float32; small whole numbers
    where ``integer``."""
    hi, seq, di = shape["heads"], shape["seq"], shape["dim"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    dims = [(n, 1, hi, seq, di), (n, 1, seq, di), (n, 1, seq, hi)]
    if integer:
        qi, ki, w = (jax.random.randint(k, d, -2, 3).astype(jnp.float32)
                     for k, d in zip(ks, dims))
    else:
        qi, ki, w = (jax.random.normal(k, d, jnp.float32)
                     for k, d in zip(ks, dims))
    return qi.astype(jnp.bfloat16), ki.astype(jnp.bfloat16), w


SUPER_ROWS, ROWS = 2048, 128


def _super_blocks(seq):
    """[(extent, first rows of its blocks)], one a super block of ``seq``
    queries (one super block where 2048 does not divide ``seq``)."""
    sup = SUPER_ROWS if seq % SUPER_ROWS == 0 else seq
    return [(r0 + sup, r0 + ROWS * jnp.arange(sup // ROWS))
            for r0 in range(0, seq, sup)]


def scores_xla(qi, ki, w):
    """The index scores of one sequence in this tool's row blocks: a list
    of [blocks, rows, extent] float32, one a super block."""
    return [lax.scan(lambda c, row0, e=extent: (c, sa.index_scores(
        lax.dynamic_slice_in_dim(qi, row0, ROWS, axis=1), ki[:e],
        lax.dynamic_slice_in_dim(w, row0, ROWS, axis=0))), (), starts)[1]
        for extent, starts in _super_blocks(ki.shape[0])]


def search_xla(scores, seq, topk):
    """The threshold search and packing on given scores, in this tool's
    row blocks."""
    blocks = _super_blocks(seq)
    outs = []
    for (extent, starts), s in zip(blocks, scores):
        outs.append(lax.scan(lambda c, x, e=extent: (c, sa.pack_selection(
            jnp.pad(sa.select_rows(x[1], x[0], topk),
                    ((0, 0), (0, seq - e))))), (), (starts, s))[1]
            .reshape(-1, seq // 8))
    return jnp.concatenate(outs)


def time_it(fn, args, n_inputs, calls):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return out, 1e3 * float(np.median(ts)) / n_inputs, compile_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--seq", type=int, default=None,
                    help="keys of the layer (a multiple of 128)")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.tiny:
        sys.exit("select_micro: no TPU here (--tiny runs the control flow "
                 "on the CPU, no timing)")
    shape = dict(TINY if a.tiny else FULL)
    if a.seq:
        shape["seq"] = a.seq
    seq, topk, n = shape["seq"], shape["topk"], shape["inputs"]
    head = {"device": dev.platform, "kind": dev.device_kind, **shape}
    print(json.dumps(head), flush=True)
    interpret = (pltpu.force_tpu_interpret_mode()
                 if dev.platform != "tpu" else None)
    if interpret is not None:
        interpret.__enter__()
    rec = [head]

    def report(piece, ms, compile_s, **more):
        line = {"piece": piece, "ms": round(ms, 4),
                "compile_s": round(compile_s, 2), **more}
        rec.append(line)
        print(json.dumps(line), flush=True)

    def per_input(f):
        return jax.jit(lambda *xs: lax.map(lambda x: f(*x), xs))

    kernel = per_input(lambda q, k, w: pk.select_topk_tpu(q, k, w, topk))

    qi, ki, w = operands(n, shape, 0, integer=False)
    _, ms, cs = time_it(per_input(lambda q, k, w: q * 2), (qi, ki, w), n,
                        a.calls)
    report("floor (a pass over QI)", ms, cs)
    scores, ms, cs = time_it(per_input(lambda q, k, w: scores_xla(
        q[0], k[0], w[0])), (qi, ki, w), n, a.calls)
    report("xla scores (the tool's row blocks)", ms, cs)
    _, ms, cs = time_it(jax.jit(lambda s: lax.map(
        lambda x: search_xla(x, seq, topk), s)), (scores,), n, a.calls)
    report("xla search and packing (the tool's row blocks)", ms, cs)
    del scores
    want, ms, cs = time_it(per_input(lambda q, k, w: sa.select_topk(
        q[0], k[0], w[0], topk)), (qi, ki, w), n, a.calls)
    report("xla op", ms, cs)
    want = np.asarray(want)
    got, ms, cs = time_it(kernel, (qi, ki, w), n, a.calls)
    report("kernel", ms, cs,
           bytes_differing=int((np.asarray(got)[:, 0] != want).sum()))
    qi, ki, w = operands(n, shape, 1, integer=True)
    want = np.asarray(per_input(lambda q, k, w: sa.select_topk(
        q[0], k[0], w[0], topk))(qi, ki, w))
    got, ms, cs = time_it(kernel, (qi, ki, w), n, a.calls)
    equal = bool((np.asarray(got)[:, 0] == want).all())
    report("kernel, whole-number operands (the tie path in every block)",
           ms, cs, equal_to_xla=equal)
    if interpret is not None:
        interpret.__exit__(None, None, None)
    if a.out:
        with open(a.out, "w") as f:
            json.dump(rec, f, indent=1)
    if not equal:
        sys.exit("select_micro: the kernel's bits differ from the XLA op's "
                 "on exact-integer operands")


if __name__ == "__main__":
    main()
