"""Optimizer update ops + AMP loss-scaling ops.

Reference: paddle/fluid/operators/optimizers/{sgd,momentum,adam,adamw,lamb,
adagrad,rmsprop,ftrl,lars_momentum,dpsgd}_op.cc (SURVEY §2.5) and
operators/amp/{check_finite_and_unscale_op,update_loss_scaling_op}.cu.
Each op consumes (param, grad, states...) and emits new values; the executor
writes the outputs back to the scope — the functional analog of the
reference's in-place ParamOut aliasing.  All are marked non-differentiable.
XLA fuses the whole optimizer phase into a couple of elementwise kernels, the
same effect as fuse_adam_op_pass/coalesce_grad_tensor_pass for free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_op


def _p(ins, slot):
    return ins[slot][0]


def _mp_param(ins):
    """Multi-precision entry (reference sgd_op.h MultiPrecision path):
    when a MasterParam rides in, the update computes on the fp32 master
    and the low-precision param is just a VIEW of it — (compute_param,
    fp32_grad, master?) with the grad widened so accumulation never
    happens in bf16."""
    master = ins.get("MasterParam", [None])[0]
    p = master if master is not None else _p(ins, "Param")
    g = _p(ins, "Grad")
    if master is not None and g.dtype != master.dtype:
        g = g.astype(master.dtype)
    return p, g, master


def _mp_outs(outs, ins, master_new):
    """Split the updated master into (bf16 ParamOut view, fp32
    MasterParamOut)."""
    lo = _p(ins, "Param").dtype
    outs["ParamOut"] = [master_new.astype(lo)]
    outs["MasterParamOut"] = [master_new]
    return outs


@register_op("sgd", differentiable=False)
def _sgd(ins, attrs, ctx):
    p, g, master = _mp_param(ins)
    lr = _p(ins, "LearningRate").reshape(())
    p_new = p - lr * g
    if master is not None:
        return _mp_outs({}, ins, p_new)
    return {"ParamOut": [p_new]}


@register_op("momentum", differentiable=False)
def _momentum(ins, attrs, ctx):
    p, g, master = _mp_param(ins)
    v = _p(ins, "Velocity")
    lr = _p(ins, "LearningRate").reshape(())
    mu = attrs.get("mu", 0.9)
    rd = attrs.get("regularization_coeff", 0.0)
    if attrs.get("regularization_method", "") == "l2_decay" and rd:
        g = g + rd * p
    v_new = mu * v + g
    if attrs.get("use_nesterov", False):
        p_new = p - lr * (g + mu * v_new)
    else:
        p_new = p - lr * v_new
    outs = {"VelocityOut": [v_new]}
    if master is not None:
        return _mp_outs(outs, ins, p_new)
    outs["ParamOut"] = [p_new]
    return outs


@register_op("lars_momentum", differentiable=False)
def _lars_momentum(ins, attrs, ctx):
    p, g, v = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "Velocity")
    lr = _p(ins, "LearningRate").reshape(())
    mu = attrs.get("mu", 0.9)
    coeff = attrs.get("lars_coeff", 0.001)
    decay = attrs.get("lars_weight_decay", 0.0005)
    eps = attrs.get("epsilon", 0.0)
    pn = jnp.sqrt(jnp.sum(jnp.square(p)))
    gn = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = jnp.where(pn > 0, jnp.where(
        gn > 0, coeff * pn / (gn + decay * pn + eps), 1.0), 1.0)
    v_new = mu * v + lr * local_lr * (g + decay * p)
    return {"ParamOut": [p - v_new], "VelocityOut": [v_new]}


@register_op("adam", differentiable=False)
def _adam(ins, attrs, ctx):
    p, g, master = _mp_param(ins)
    m, v = _p(ins, "Moment1"), _p(ins, "Moment2")
    b1p, b2p = _p(ins, "Beta1Pow").reshape(()), _p(ins, "Beta2Pow").reshape(())
    lr = _p(ins, "LearningRate").reshape(())
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * jnp.square(g)
    # reference adam_op.h: lr_t = lr * sqrt(1 - b2^t) / (1 - b1^t)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    p_new = p - lr_t * m_new / (jnp.sqrt(v_new) + eps)
    outs = {"Moment1Out": [m_new], "Moment2Out": [v_new],
            "Beta1PowOut": [(b1p * b1).reshape(1)],
            "Beta2PowOut": [(b2p * b2).reshape(1)]}
    if master is not None:
        return _mp_outs(outs, ins, p_new)
    outs["ParamOut"] = [p_new]
    return outs


@register_op("adamw", differentiable=False)
def _adamw(ins, attrs, ctx):
    p, _, master = _mp_param(ins)
    coeff = attrs.get("coeff", 0.01)
    lr = _p(ins, "LearningRate").reshape(())
    out = _adam(ins, attrs, ctx)
    if not attrs.get("with_decay", True):
        return out
    # decoupled weight decay applied against the pre-update (master) param
    if master is not None:
        return _mp_outs(out, ins, out["MasterParamOut"][0] - lr * coeff * p)
    out["ParamOut"] = [out["ParamOut"][0] - lr * coeff * p]
    return out


@register_op("adagrad", differentiable=False)
def _adagrad(ins, attrs, ctx):
    p, g, mom = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "Moment")
    lr = _p(ins, "LearningRate").reshape(())
    eps = attrs.get("epsilon", 1e-6)
    mom_new = mom + jnp.square(g)
    return {"ParamOut": [p - lr * g / (jnp.sqrt(mom_new) + eps)],
            "MomentOut": [mom_new]}


@register_op("rmsprop", differentiable=False)
def _rmsprop(ins, attrs, ctx):
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    ms, mom = _p(ins, "MeanSquare"), _p(ins, "Moment")
    lr = _p(ins, "LearningRate").reshape(())
    rho = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    mu = attrs.get("momentum", 0.0)
    ms_new = rho * ms + (1 - rho) * jnp.square(g)
    if attrs.get("centered", False):
        mg = _p(ins, "MeanGrad")
        mg_new = rho * mg + (1 - rho) * g
        denom = jnp.sqrt(ms_new - jnp.square(mg_new) + eps)
        mom_new = mu * mom + lr * g / denom
        return {"ParamOut": [p - mom_new], "MeanSquareOut": [ms_new],
                "MomentOut": [mom_new], "MeanGradOut": [mg_new]}
    mom_new = mu * mom + lr * g / jnp.sqrt(ms_new + eps)
    return {"ParamOut": [p - mom_new], "MeanSquareOut": [ms_new],
            "MomentOut": [mom_new]}


@register_op("lamb", differentiable=False)
def _lamb(ins, attrs, ctx):
    p, g, master = _mp_param(ins)
    m, v = _p(ins, "Moment1"), _p(ins, "Moment2")
    b1p, b2p = _p(ins, "Beta1Pow").reshape(()), _p(ins, "Beta2Pow").reshape(())
    lr = _p(ins, "LearningRate").reshape(())
    b1, b2 = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * jnp.square(g)
    m_hat = m_new / (1 - b1p)
    v_hat = v_new / (1 - b2p)
    r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    ratio = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
    outs = {"Moment1Out": [m_new], "Moment2Out": [v_new],
            "Beta1PowOut": [(b1p * b1).reshape(1)],
            "Beta2PowOut": [(b2p * b2).reshape(1)]}
    if master is not None:
        return _mp_outs(outs, ins, p - lr * ratio * r)
    outs["ParamOut"] = [p - lr * ratio * r]
    return outs


@register_op("ftrl", differentiable=False)
def _ftrl(ins, attrs, ctx):
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    sq, lin = _p(ins, "SquaredAccumulator"), _p(ins, "LinearAccumulator")
    lr = _p(ins, "LearningRate").reshape(())
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    power = attrs.get("lr_power", -0.5)
    sq_new = sq + jnp.square(g)
    sigma = (jnp.power(sq_new, -power) - jnp.power(sq, -power)) / lr
    lin_new = lin + g - sigma * p
    quad = jnp.power(sq_new, -power) / lr + 2 * l2
    pre = jnp.clip(lin_new, -l1, l1) - lin_new
    p_new = jnp.where(jnp.abs(lin_new) > l1, pre / quad, 0.0)
    return {"ParamOut": [p_new], "SquaredAccumOut": [sq_new],
            "LinearAccumOut": [lin_new]}


@register_op("dpsgd", differentiable=False)
def _dpsgd(ins, attrs, ctx):
    # differentially-private SGD (optimizers/dpsgd_op.cc): clip + noise
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    lr = _p(ins, "LearningRate").reshape(())
    clip = attrs.get("clip", 10.0)
    sigma = attrs.get("sigma", 1.0)
    gn = jnp.sqrt(jnp.sum(jnp.square(g)))
    g = g / jnp.maximum(1.0, gn / clip)
    key = ctx.key_for(attrs.get("op_seed", 0))
    noise = jax.random.normal(key, g.shape, g.dtype) * sigma * clip
    return {"ParamOut": [p - lr * (g + noise)]}


# ---------------------------------------------------------------------------
# AMP dynamic loss scaling (operators/amp/*)
# ---------------------------------------------------------------------------
@register_op("check_finite_and_unscale", differentiable=False)
def _check_finite_and_unscale(ins, attrs, ctx):
    scale = _p(ins, "Scale").reshape(())
    outs, found_inf = [], jnp.zeros((), jnp.bool_)
    for x in ins["X"]:
        finite = jnp.all(jnp.isfinite(x))
        found_inf = jnp.logical_or(found_inf, jnp.logical_not(finite))
        outs.append(x / scale)
    return {"Out": outs, "FoundInfinite": [found_inf.reshape(1)]}


@register_op("update_loss_scaling", differentiable=False)
def _update_loss_scaling(ins, attrs, ctx):
    found_inf = _p(ins, "FoundInfinite").reshape(())
    scale = _p(ins, "PrevLossScaling").reshape(())
    good = _p(ins, "InGoodSteps").reshape(())
    bad = _p(ins, "InBadSteps").reshape(())
    incr_every = attrs.get("incr_every_n_steps", 1000)
    decr_every = attrs.get("decr_every_n_nan_or_inf", 2)
    incr_ratio = attrs.get("incr_ratio", 2.0)
    decr_ratio = attrs.get("decr_ratio", 0.5)

    good_new = jnp.where(found_inf, 0, good + 1)
    bad_new = jnp.where(found_inf, bad + 1, 0)
    scale_up = jnp.where(good_new >= incr_every, scale * incr_ratio, scale)
    good_new = jnp.where(good_new >= incr_every, 0, good_new)
    scale_dn = jnp.where(bad_new >= decr_every,
                         jnp.maximum(scale * decr_ratio, 1.0), scale_up)
    bad_new = jnp.where(bad_new >= decr_every, 0, bad_new)
    outs = [jnp.where(found_inf, jnp.zeros_like(x), x) for x in ins["X"]]
    return {"Out": outs, "LossScaling": [scale_dn.reshape(1)],
            "OutGoodSteps": [good_new.reshape(1)],
            "OutBadSteps": [bad_new.reshape(1)]}


@register_op("dgc_momentum", differentiable=False)
def _dgc_momentum(ins, attrs, ctx):
    """Deep Gradient Compression momentum (operators/optimizers/
    dgc_momentum_op.cc + operators/dgc_op.cc).  Momentum correction +
    error-feedback top-k sparsification; the surviving gradient mass is
    all-reduced.  On ICI the sparse NCCL encoding becomes a dense psum of
    the masked tensor — bandwidth-optimal sparse collectives don't exist on
    the mesh fabric, so the compression here preserves the *optimization*
    semantics (momentum correction, masking, error feedback) rather than
    wire format.  Before rampup_begin_step it is plain momentum."""
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    u, v = _p(ins, "U"), _p(ins, "V")
    lr = _p(ins, "LearningRate").reshape(())
    step = _p(ins, "CurrentStep").reshape(())
    mu = attrs.get("mu", 0.9)
    sparsity = attrs.get("sparsity", 0.999)
    rampup = attrs.get("rampup_begin_step", 0.0)
    use_nesterov = attrs.get("use_nesterov", False)

    # --- DGC branch: local momentum correction + top-k masking ------------
    u_corr = mu * u + g                       # momentum correction
    v_acc = v + u_corr                        # error accumulation
    flat = jnp.abs(v_acc).reshape(-1)
    thr = jnp.quantile(flat.astype(jnp.float32), sparsity)
    mask = (jnp.abs(v_acc) >= thr).astype(v_acc.dtype)
    encoded = v_acc * mask
    axis = ctx.axis_for_ring(attrs.get("ring_id", 0))
    if axis is not None:
        encoded = jax.lax.psum(encoded, axis_name=axis)
    dgc_p = p - lr * encoded
    dgc_u = u_corr * (1.0 - mask)
    dgc_v = v_acc * (1.0 - mask)

    # --- pre-rampup branch: vanilla (all-reduced) momentum ----------------
    g_sync = jax.lax.psum(g, axis_name=axis) if axis is not None else g
    v_mom = mu * u + g_sync
    mom_p = p - lr * ((g_sync + mu * v_mom) if use_nesterov else v_mom)

    use_dgc = step >= rampup
    sel = lambda a, b: jnp.where(use_dgc, a, b)
    return {"ParamOut": [sel(dgc_p, mom_p)], "UOut": [sel(dgc_u, v_mom)],
            "VOut": [sel(dgc_v, jnp.zeros_like(v))]}


@register_op("localsgd_select", differentiable=False)
def _localsgd_select(ins, attrs, ctx):
    """LocalSGD periodic parameter averaging gate (see
    fleet/meta_optimizers/localsgd_optimizer.py): lands the pre-computed
    ring average only on every k-th step after begin_step."""
    p, avg = _p(ins, "Param"), _p(ins, "Avg")
    step = _p(ins, "Step").reshape(())
    k = attrs.get("k_steps", 1.0)
    begin = attrs.get("begin_step", 1.0)
    do_sync = jnp.logical_and(step >= begin,
                              jnp.mod(step, jnp.maximum(k, 1.0)) == 0)
    return {"ParamOut": [jnp.where(do_sync, avg, p)]}


@register_op("average_accumulates", differentiable=False)
def _average_accumulates(ins, attrs, ctx):
    """Sliding-window parameter accumulation for ModelAverage.

    Reference: paddle/fluid/operators/average_accumulates_op.h — sum_1
    accumulates the param each step; once the window fills
    (num_accumulates >= max(min_average_window,
    min(max_average_window, num_updates * average_window_rate))) the sums
    shift (sum_3 <- sum_2 <- sum_1 <- 0).  Branch-free via jnp.where so the
    whole thing stays one fused XLA kernel."""
    p = _p(ins, "param")
    s1, s2, s3 = _p(ins, "in_sum_1"), _p(ins, "in_sum_2"), _p(ins, "in_sum_3")
    na = _p(ins, "in_num_accumulates").reshape(()).astype(jnp.float32)
    ona = _p(ins, "in_old_num_accumulates").reshape(()).astype(jnp.float32)
    nu = _p(ins, "in_num_updates").reshape(()).astype(jnp.float32)
    rate = attrs.get("average_window", 0.0)
    min_w = attrs.get("min_average_window", 10000)
    max_w = attrs.get("max_average_window", 10000)

    s1 = s1 + p
    na = na + 1.0
    nu = nu + 1.0
    # precision shuffle every 16384 updates (reference kMaxNumAccumulates)
    shuffle = jnp.mod(nu, 16384.0) == 0
    s2 = jnp.where(shuffle, s2 + s1, s2)
    s1 = jnp.where(shuffle, jnp.zeros_like(s1), s1)
    # window overflow: sum_3 REPLACED by the completed window (s1+s2)
    window = jnp.minimum(jnp.float32(max_w), nu * rate)
    shift = jnp.logical_and(na >= min_w, na >= window)
    out_s1 = jnp.where(shift, jnp.zeros_like(s1), s1)
    out_s2 = jnp.where(shift, jnp.zeros_like(s2), s2)
    out_s3 = jnp.where(shift, s1 + s2, s3)
    out_ona = jnp.where(shift, na, ona)
    out_na = jnp.where(shift, jnp.float32(0.0), na)
    one = lambda x: x.reshape(1)
    return {"out_sum_1": [out_s1], "out_sum_2": [out_s2],
            "out_sum_3": [out_s3], "out_num_accumulates": [one(out_na)],
            "out_old_num_accumulates": [one(out_ona)],
            "out_num_updates": [one(nu)]}


# ---------------------------------------------------------------------------
# SkipUpdate gating: GradientMergeOptimizer attaches a boolean SkipUpdate
# input to the update ops it appends; on skip steps EVERY output (param,
# moments, beta pows) keeps its old value — matching the reference, which
# runs the optimizer ops only on the k-th step (optimizer.py:4969) instead
# of feeding them zero grads (zero grads still decay Adam/momentum state).
# Applied generically by the executor (run_block_ops) for any op carrying
# a SkipUpdate input, so it works for every update-op family regardless of
# registration order.
# ---------------------------------------------------------------------------

def apply_skip_update(ins, outs):
    """where(skip, old, new) every 'XOut' output against its 'X' input."""
    skip_in = ins.get("SkipUpdate")
    if not skip_in:
        return outs
    skip = skip_in[0].reshape(()).astype(bool)
    gated_outs = {}
    for slot, vals in outs.items():
        src = slot[:-3] if slot.endswith("Out") else None
        olds = ins.get(src, []) if src else []
        kept = []
        for i, new in enumerate(vals):
            old = olds[i] if i < len(olds) else None
            kept.append(new if old is None else jnp.where(skip, old, new))
        gated_outs[slot] = kept
    return gated_outs


@register_op("adadelta", differentiable=False)
def _adadelta(ins, attrs, ctx):
    """optimizers/adadelta_op.cc: accumulated grad/update RMS ratios."""
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    avg_sq_g = _p(ins, "AvgSquaredGrad")
    avg_sq_u = _p(ins, "AvgSquaredUpdate")
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    new_g = rho * avg_sq_g + (1 - rho) * g * g
    update = -jnp.sqrt(avg_sq_u + eps) / jnp.sqrt(new_g + eps) * g
    new_u = rho * avg_sq_u + (1 - rho) * update * update
    return {"ParamOut": [p + update], "AvgSquaredGradOut": [new_g],
            "AvgSquaredUpdateOut": [new_u]}


@register_op("decayed_adagrad", differentiable=False)
def _decayed_adagrad(ins, attrs, ctx):
    """optimizers/decayed_adagrad_op.cc: adagrad with decaying accumulator."""
    p, g, m = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "Moment")
    lr = _p(ins, "LearningRate").reshape(())
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    new_m = decay * m + (1 - decay) * g * g
    return {"ParamOut": [p - lr * g / (jnp.sqrt(new_m) + eps)],
            "MomentOut": [new_m]}
