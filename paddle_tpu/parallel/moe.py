"""Mixture-of-Experts with expert parallelism over the `ep` mesh axis.

No reference analog (qingshui/Paddle predates MoE serving at scale); this
fills the `ep` axis declared in parallel/mesh.py.  The design is the
GShard/Switch recipe shaped for XLA:

* top-k gating with a capacity limit — everything static-shaped: routing
  builds dense dispatch/combine tensors [T, E, C] instead of ragged
  gathers, so XLA tiles the whole layer onto the MXU;
* expert parallelism = two `lax.all_to_all`s: dispatch sends each expert's
  token slots to the device that owns it, the expert FFNs run as one
  batched einsum over the local expert shard, and the combine a2a returns
  slot outputs to the token owners;
* an auxiliary load-balancing loss (mean gate fraction x mean dispatch
  fraction per expert, scaled by E) — the standard Switch aux loss.

Works on a single device too (no axis bound -> skip the all_to_alls), so
the same layer code runs in tests, single-chip, and ep-sharded meshes.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def top1_routing(logits, capacity: int):
    """Switch-style top-1 routing.

    logits: [T, E] gate scores.  Returns (dispatch [T, E, C] one-hot,
    combine [T, E, C] weights, aux_loss scalar).  Tokens beyond an
    expert's capacity C are dropped (combine weight 0) — the documented
    Switch behavior, which keeps every shape static for XLA.
    """
    t, e = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)               # [T]
    expert_1h = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)
    # position of each token within its expert's queue
    pos = jnp.cumsum(expert_1h, axis=0) * expert_1h       # [T, E], 1-based
    in_cap = (pos <= capacity).astype(jnp.float32) * expert_1h
    slot = jax.nn.one_hot((pos - 1.0).astype(jnp.int32), capacity,
                          dtype=jnp.float32)              # [T, E, C]
    dispatch = slot * in_cap[..., None]
    gate_val = (gates * expert_1h).sum(-1, keepdims=True)  # [T, 1]
    combine = dispatch * gate_val[..., None]
    # Switch aux loss: E * sum_e(fraction_routed_e * mean_gate_e)
    frac_routed = expert_1h.mean(axis=0)
    mean_gate = gates.mean(axis=0)
    aux = e * jnp.sum(frac_routed * mean_gate)
    return dispatch, combine, aux


def moe_ffn(x, gate_w, w_in, w_out, axis_name: Optional[str] = None,
            capacity_factor: float = 1.25,
            activation=jax.nn.gelu) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One MoE FFN block.

    x: [T, D] local tokens.  gate_w: [D, E].  w_in: [E_local, D, F],
    w_out: [E_local, F, D] — this rank's expert shard (E_local = E / ep;
    E_local = E when axis_name is None).  Returns (out [T, D], aux_loss).
    """
    t, d = x.shape
    n = lax.axis_size(axis_name) if axis_name is not None else 1
    e_local = w_in.shape[0]
    e = e_local * n
    capacity = max(1, int(math.ceil(t / e * capacity_factor)))

    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)   # [T, E]
    dispatch, combine, aux = top1_routing(logits, capacity)

    # [T, E, C] x [T, D] -> [E, C, D] expert queues
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    if axis_name is not None:
        # each device keeps rows for its local experts, receives the same
        # rows from every peer: [E, C, D] -> [E/n, n*C, D]
        expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                                   concat_axis=1, tiled=True)
    h = jnp.einsum("ecd,edf->ecf", expert_in, w_in.astype(jnp.float32))
    h = activation(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(jnp.float32))
    if axis_name is not None:
        expert_out = lax.all_to_all(expert_out, axis_name, split_axis=1,
                                    concat_axis=0, tiled=True)
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out.astype(x.dtype), aux.astype(jnp.float32)


def moe_partition_rules(axis: str = "ep"):
    """MoE placement through the shared rule engine
    (parallel/sharding.py): the gate replicates (every device routes),
    expert weights shard their expert dim over ``ep`` — feed these to
    ``match_partition_rules``/``ShardingPlan`` instead of hand-placing
    each array."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"(^|[_/.])gate(_w)?$", P()),
        (r"(^|[_/.])w_in$", P(axis, None, None)),
        (r"(^|[_/.])w_out$", P(axis, None, None)),
    ]


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    e_local: Optional[int] = None):
    """Initializer helper: returns (gate_w [D, E], w_in [E_l, D, F],
    w_out [E_l, F, D]) with fan-in scaling."""
    e_local = n_experts if e_local is None else e_local
    k1, k2, k3 = jax.random.split(key, 3)
    gate = jax.random.normal(k1, (d_model, n_experts)) / math.sqrt(d_model)
    w_in = jax.random.normal(
        k2, (e_local, d_model, d_ff)) / math.sqrt(d_model)
    w_out = jax.random.normal(
        k3, (e_local, d_ff, d_model)) / math.sqrt(d_ff)
    return gate, w_in, w_out
