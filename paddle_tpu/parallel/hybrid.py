"""Hybrid-parallel transformer training step: dp x pp x tp x sp on one mesh.

This is the TPU-native replacement for the reference's entire multi-device
execution stack (SURVEY §2.9): ParallelExecutor SSA-graph DP
(parallel_executor.cc), Fleet collective DP (c_allreduce ops),
PipelineOptimizer/SectionWorker GPipe (optimizer.py:3693,
section_worker.cc:44-112), sharding_optimizer.py ZeRO — plus tensor and
sequence/context parallelism, which the reference does NOT have
(SURVEY §2.9 "NOT PRESENT") and which this build adds as a new capability.

Design (scaling-book recipe, explicit-collectives flavor):
  * one `jax.sharding.Mesh` with axes (dp, pp, tp, sp); any axis may be 1
  * the WHOLE train step — forward, backward, optimizer — is a single
    `shard_map`-ed function; XLA schedules ICI collectives
  * dp: batch sharded; gradients psum over dp (the AllReduceOpHandle analog)
  * pp: GPipe — layers stacked on a leading stage axis sharded over pp;
    microbatches stream through `lax.ppermute` (the send_v2/recv_v2 analog);
    schedule mirrors section_worker.cc's F-then-B but is autodiff-derived:
    jax.grad of the forward pipeline transposes each ppermute into the
    reverse-direction ppermute, giving the backward pipeline for free
  * tp: Megatron column/row-parallel MLP + head-sharded attention; the
    row-parallel psum is the c_allreduce_sum that TP would issue
  * sp: sequence dim sharded; exact attention via ring_attention (K/V blocks
    rotate over ICI with online softmax)
  * optimizer states live sharded exactly like their params (ZeRO-for-free
    on the pp/tp axes, the sharding_optimizer.py analog)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ring_attention import ring_attention
from .mesh import set_current_mesh

AXES = ("dp", "pp", "tp", "sp")


@dataclasses.dataclass
class TransformerConfig:
    vocab: int = 128
    d_model: int = 64
    n_heads: int = 4
    d_ff: int = 128
    n_layers: int = 2          # total; must divide by pp size
    seq_len: int = 32          # global
    batch: int = 8             # global
    causal: bool = True
    dtype: Any = jnp.float32   # param dtype (bf16 for perf runs)
    remat: bool = True         # jax.checkpoint each layer (recompute analog)
    lr: float = 1e-3
    microbatches: int = 2      # GPipe microbatches per pp stage
    sp_mode: str = "ring"      # "ring" (O(T/n) memory, ppermute overlap)
                               # or "ulysses" (all-to-all head re-shard;
                               # needs the LOCAL head count divisible by
                               # sp — i.e. (n_heads / tp) % sp == 0, since
                               # heads are already tp-sharded in _layer)


def mesh_axes_for(n_devices: int) -> Dict[str, int]:
    """Factor a device count onto (dp, pp, tp, sp), preferring to exercise
    every parallelism dimension (pp/tp/sp first, leftover to dp)."""
    n = int(n_devices)
    axes = {"dp": 1, "pp": 1, "tp": 1, "sp": 1}
    for name in ("pp", "tp", "sp"):
        if n % 2 == 0 and n > 1:
            axes[name] = 2
            n //= 2
    axes["dp"] = n
    return axes


def build_hybrid_mesh(n_devices: Optional[int] = None, devices=None,
                      axes: Optional[Dict[str, int]] = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    axes = axes or mesh_axes_for(len(devices))
    shape = tuple(axes[a] for a in AXES)
    arr = np.asarray(devices).reshape(shape)
    mesh = Mesh(arr, AXES)
    set_current_mesh(mesh)
    return mesh


# ---------------------------------------------------------------------------
# Parameter schema: global shapes + PartitionSpec + which axes hold replicas
# (gradients must be psum'ed over exactly the replica axes — scaling-book
# rule; this table is the analog of the reference's per-param ring binding).
# ---------------------------------------------------------------------------

def param_schema(cfg: TransformerConfig) -> Dict[str, Tuple[tuple, P, tuple]]:
    V, D, H, F, L, T = (cfg.vocab, cfg.d_model, cfg.n_heads, cfg.d_ff,
                        cfg.n_layers, cfg.seq_len)
    Dh = D // H
    shapes = {
        "embed": (V, D), "pos": (T, D),
        "wq": (L, D, H, Dh), "wk": (L, D, H, Dh), "wv": (L, D, H, Dh),
        "wo": (L, H, Dh, D),
        "w1": (L, D, F), "b1": (L, F), "w2": (L, F, D), "b2": (L, D),
        "ln1_g": (L, D), "ln1_b": (L, D), "ln2_g": (L, D), "ln2_b": (L, D),
        "lnf_g": (D,), "lnf_b": (D,),
        "head": (D, V),
    }
    # gradients must be psum'ed over exactly the axes holding replicas
    rep = {
        "embed": ("dp", "pp", "sp"), "pos": ("dp", "pp", "tp"),
        "wq": ("dp", "sp"), "wk": ("dp", "sp"), "wv": ("dp", "sp"),
        "wo": ("dp", "sp"),
        "w1": ("dp", "sp"), "b1": ("dp", "sp"), "w2": ("dp", "sp"),
        "b2": ("dp", "sp", "tp"),
        "ln1_g": ("dp", "sp", "tp"), "ln1_b": ("dp", "sp", "tp"),
        "ln2_g": ("dp", "sp", "tp"), "ln2_b": ("dp", "sp", "tp"),
        "lnf_g": ("dp", "pp", "sp", "tp"),
        "lnf_b": ("dp", "pp", "sp", "tp"),
        "head": ("dp", "pp", "sp"),
    }
    # partition specs come from the SAME rule engine every other plane
    # uses (parallel/sharding.py HYBRID_RULES) — the per-module table and
    # BuildStrategy.sharding are one mechanism, not two
    from .sharding import HYBRID_RULES, match_partition_rules
    specs = match_partition_rules(HYBRID_RULES, shapes,
                                  on_unmatched="raise")
    return {n: (shapes[n], specs[n], rep[n]) for n in shapes}


def init_params(cfg: TransformerConfig, key=None) -> Dict[str, jax.Array]:
    key = key if key is not None else jax.random.PRNGKey(0)
    out = {}
    for i, (name, (shape, _, _)) in enumerate(sorted(param_schema(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_g"):
            out[name] = jnp.ones(shape, cfg.dtype)
        elif name.endswith("_b") or name.startswith("b"):
            out[name] = jnp.zeros(shape, cfg.dtype)
        else:
            scale = 0.02
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * scale).astype(cfg.dtype)
    return out


def _ln(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * g + b).astype(x.dtype)


# ---------------------------------------------------------------------------
# Per-device (shard_map body) model
# ---------------------------------------------------------------------------

def _layer(x, lp, cfg: TransformerConfig, sp_live: bool, tp_live: bool):
    """One transformer layer on local shards. x: [mb, t_loc, D]."""
    h = _ln(x, lp["ln1_g"], lp["ln1_b"])
    q = jnp.einsum("btd,dhe->bhte", h, lp["wq"])
    k = jnp.einsum("btd,dhe->bhte", h, lp["wk"])
    v = jnp.einsum("btd,dhe->bhte", h, lp["wv"])
    if sp_live:
        if cfg.sp_mode == "ulysses":
            from .ulysses import ulysses_attention
            a = ulysses_attention(q, k, v, "sp", causal=cfg.causal)
        elif cfg.sp_mode == "ring":
            a = ring_attention(q, k, v, "sp", causal=cfg.causal)
        else:
            raise ValueError(
                f"unknown sp_mode {cfg.sp_mode!r}: use 'ring' or 'ulysses'")
    else:
        from ..ops.attention import flash_attention
        from ..ops.registry import chip_site
        a = flash_attention(q, k, v, causal=cfg.causal, site=chip_site())
    o = jnp.einsum("bhte,hed->btd", a, lp["wo"])
    if tp_live:
        o = lax.psum(o, "tp")            # row-parallel proj (c_allreduce_sum)
    x = x + o
    h2 = _ln(x, lp["ln2_g"], lp["ln2_b"])
    u = jax.nn.gelu(jnp.einsum("btd,df->btf", h2, lp["w1"]) + lp["b1"])
    f = jnp.einsum("btf,fd->btd", u, lp["w2"])
    if tp_live:
        f = lax.psum(f, "tp")            # row-parallel MLP out
    return x + (f + lp["b2"]).astype(x.dtype)


def _stage_fn(x, stage_params, cfg, sp_live, tp_live):
    """Apply this pp rank's slice of layers (lax.scan over the local stack)."""
    layer = lambda carry, lp: (_layer(carry, lp, cfg, sp_live, tp_live), None)
    if cfg.remat:
        layer = jax.checkpoint(layer)
    x, _ = lax.scan(layer, x, stage_params)
    return x


def _vocab_parallel_ce(logits_local, labels, vstart, tp_live):
    """Cross entropy with the vocab dim sharded over tp.

    logits_local: [b, t, V_local]; labels: [b, t] global ids.
    logsumexp and the label logit are assembled with tp collectives —
    the vocab-parallel loss of Megatron (no reference analog).
    """
    acc = jnp.float32
    z = logits_local.astype(acc)
    # the max shift cancels in d(lse - picked); stop_gradient also sidesteps
    # pmax's missing differentiation rule
    zmax = lax.stop_gradient(z.max(-1))
    if tp_live:
        zmax = lax.stop_gradient(lax.pmax(zmax, "tp"))
    sumexp = jnp.exp(z - zmax[..., None]).sum(-1)
    if tp_live:
        sumexp = lax.psum(sumexp, "tp")
    lse = jnp.log(sumexp) + zmax
    local = labels - vstart
    vloc = z.shape[-1]
    valid = (local >= 0) & (local < vloc)
    picked = jnp.take_along_axis(
        z, jnp.clip(local, 0, vloc - 1)[..., None], axis=-1)[..., 0]
    picked = jnp.where(valid, picked, 0.0)
    if tp_live:
        picked = lax.psum(picked, "tp")
    return (lse - picked).mean()


def _forward_local(params, tokens, labels, cfg: TransformerConfig,
                   axis_sizes: Dict[str, int]):
    """Per-device forward + loss. tokens/labels: [b_loc, t_loc] int32."""
    S = axis_sizes["pp"]
    tp_live = axis_sizes["tp"] > 1
    sp_live = axis_sizes["sp"] > 1
    stage = lax.axis_index("pp")

    # vocab-parallel embedding (c_embedding pattern, collective_ops.py)
    vloc = params["embed"].shape[0]
    vstart = lax.axis_index("tp") * vloc
    local_ids = tokens - vstart
    ok = (local_ids >= 0) & (local_ids < vloc)
    emb = jnp.take(params["embed"], jnp.clip(local_ids, 0, vloc - 1), axis=0)
    emb = jnp.where(ok[..., None], emb, 0.0)
    if tp_live:
        emb = lax.psum(emb, "tp")
    x = (emb + params["pos"][None, :emb.shape[1]]).astype(cfg.dtype)

    # --- GPipe over pp: microbatch stream threaded by ppermute -------------
    b = x.shape[0]
    M = min(cfg.microbatches, b)
    if b % M != 0:
        raise ValueError(
            f"local batch {b} not divisible by microbatches {M}")
    mb = b // M
    x_mb = x[: M * mb].reshape(M, mb, *x.shape[1:])
    sp_names = ("wq", "wk", "wv", "wo", "w1", "b1", "w2", "b2",
                "ln1_g", "ln1_b", "ln2_g", "ln2_b")
    stage_params = {n: params[n] for n in sp_names}

    nxt = [(i, (i + 1) % S) for i in range(S)]
    carry = jnp.zeros_like(x_mb[0])
    outs = []
    for step in range(M + S - 1):
        inject = x_mb[min(step, M - 1)]
        stage_in = jnp.where(stage == 0, inject, carry)
        y = _stage_fn(stage_in, stage_params, cfg, sp_live, tp_live)
        if step >= S - 1:
            outs.append(y)                      # valid on the LAST stage
        if S > 1:
            carry = lax.ppermute(y, "pp", nxt)  # send_v2/recv_v2 analog
    h = jnp.concatenate(outs, axis=0)           # [M*mb, t_loc, D]

    h = _ln(h, params["lnf_g"], params["lnf_b"])
    logits = jnp.einsum("btd,dv->btv", h, params["head"])
    lbl = labels[: M * mb]
    loss = _vocab_parallel_ce(logits, lbl, vstart, tp_live)

    # only the last pp stage computed the real loss; zero elsewhere, then
    # psum over pp broadcasts it (garbage on other stages masked by where)
    loss = jnp.where(stage == S - 1, loss, 0.0)
    if S > 1:
        loss = lax.psum(loss, "pp")
    # average over dp and sp shards (per-token mean over the global batch)
    loss = lax.pmean(loss, "dp")
    loss = lax.pmean(loss, "sp")
    return loss


# ---------------------------------------------------------------------------
# Train step factory
# ---------------------------------------------------------------------------

def make_train_step(mesh: Mesh, cfg: TransformerConfig):
    """Returns (params, opt_state, step_fn); step_fn(params, opt, tok, lbl)
    -> (params, opt, loss) — jitted, fully sharded, donates params."""
    schema = param_schema(cfg)
    axis_sizes = {a: mesh.shape[a] for a in AXES}

    def local_step(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: _forward_local(p, tokens, labels, cfg, axis_sizes)
        )(params)
        # psum each grad over exactly its replica axes (schema column 3)
        for name, (_, _, rep_axes) in schema.items():
            live = tuple(a for a in rep_axes if axis_sizes[a] > 1)
            if live:
                grads[name] = lax.psum(grads[name], live)
        # Adam, states sharded like params (ZeRO-on-pp/tp for free)
        m, v, t = opt_state
        t = t + 1
        b1, b2, eps = 0.9, 0.999, 1e-8
        new_m, new_v, new_p = {}, {}, {}
        for k in params:
            g = grads[k].astype(jnp.float32)
            new_m[k] = b1 * m[k] + (1 - b1) * g
            new_v[k] = b2 * v[k] + (1 - b2) * g * g
            mhat = new_m[k] / (1 - b1 ** t)
            vhat = new_v[k] / (1 - b2 ** t)
            new_p[k] = (params[k].astype(jnp.float32)
                        - cfg.lr * mhat / (jnp.sqrt(vhat) + eps)
                        ).astype(params[k].dtype)
        return new_p, (new_m, new_v, t), loss

    pspecs = {n: s[1] for n, s in schema.items()}
    data_spec = P("dp", "sp")
    opt_spec = (pspecs, pspecs, P())
    from .api import compat_shard_map
    sharded = compat_shard_map(
        local_step, mesh=mesh,
        in_specs=(pspecs, opt_spec, data_spec, data_spec),
        out_specs=(pspecs, opt_spec, P()),
        check_vma=False)
    step_fn = jax.jit(sharded, donate_argnums=(0, 1))

    params = init_params(cfg)
    params = {k: jax.device_put(v, NamedSharding(mesh, pspecs[k]))
              for k, v in params.items()}
    def zeros_like_sharded():
        # fresh arrays each time: device_put dedupes identical buffers, and a
        # shared buffer would be donated twice by donate_argnums
        return {k: jax.device_put(jnp.zeros(v.shape, jnp.float32),
                                  NamedSharding(mesh, pspecs[k]))
                for k, v in params.items()}
    opt_state = (zeros_like_sharded(), zeros_like_sharded(),
                 jnp.zeros((), jnp.int32))
    return params, opt_state, step_fn


def demo_batch(cfg: TransformerConfig, mesh: Mesh, seed=0):
    rng = np.random.RandomState(seed)
    tok = rng.randint(0, cfg.vocab, (cfg.batch, cfg.seq_len)).astype(np.int32)
    lbl = np.roll(tok, -1, axis=1).astype(np.int32)
    sh = NamedSharding(mesh, P("dp", "sp"))
    return jax.device_put(tok, sh), jax.device_put(lbl, sh)
