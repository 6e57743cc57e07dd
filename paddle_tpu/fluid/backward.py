"""Op-level autodiff over the Program IR.

Reference: python/paddle/fluid/backward.py:1276 `append_backward` reverse-walks
the ops of a ProgramDesc and asks each op's C++ GradOpDescMaker
(backward.py:984 -> core.get_grad_op_desc) for its grad OpDescs, inserting
`sum` ops for fan-in.  TPU-native difference: there are no hand-written grad
ops.  One *generic* grad op (`generic_grad`) computes input cotangents with
`jax.vjp` over the forward op's own lowering rule — correctness is inherited
from JAX's AD instead of 676 hand-derived kernels.  The forward runs once:
where `run_block_ops` finds a forward op and its `generic_grad` in the op
list it is tracing (`pair_grads`) it lowers the forward under `jax.vjp`
(`lower_under_vjp`) and the grad op applies the vjp that call kept.  Every
other route to a `generic_grad` (the eager tape's direct call, an `ops=`
subset that holds the grads without their forwards, an input overwritten
between the two, a `call_op` override) traces the forward lowering again
over `I_<slot>` and leaves the copy to XLA's CSE, which merges its own ops
and does not merge two Mosaic kernel calls.  Ops with special grad semantics
register `custom_grad` (registry.py) and stay on that second route.

Fan-in is a `sum` of a variable's partial gradients, except into an op that
declares the input slot in `accumulate_grads` (registry.py): the partials
its later readers' grads gave are handed to its grad op (`G_<slot>@ACC`),
whose backward adds them into the gradient it computes, so that a Pallas
backward adds them on the core instead of in a pass of its own.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp

from ..ops.registry import accumulate_slot, register_op, get_op, has_op
from . import trace
from .framework import Program, Block, Variable, Parameter

GRAD_SUFFIX = "@GRAD"


def _grad_name(name: str) -> str:
    return name + GRAD_SUFFIX


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype if not hasattr(x, "dtype")
                          else x.dtype, jnp.floating)


# ---------------------------------------------------------------------------
# the generic grad op
# ---------------------------------------------------------------------------
def lower_under_vjp(fwd_def, fwd_ins, fwd_attrs, ctx, grad_slots,
                    accumulate=()):
    """Call the forward op's lowering once, under ``jax.vjp`` over its
    differentiable inputs: ``(outs, (primal_outs, vjp_fn))``.  ``outs`` is
    everything the lowering returned (what ``run_block_ops`` writes to
    ``env``); ``primal_outs`` its differentiable part (float outputs outside
    ``nondiff_outputs``, the shape of the cotangents) and ``vjp_fn`` maps
    those cotangents to the input cotangents of ``grad_slots``.

    Differentiable: the float args of ``grad_slots`` outside
    ``nondiff_inputs`` (per arg, by runtime dtype); every other input is
    closed over, non-float and ``nondiff_outputs`` outputs ride as aux.
    ``accumulate``: the grad op's ``accumulate`` attr, the slots whose
    partial the lowering is asked (its attr ``__accumulate__``) to take as
    the cotangent of its ``accumulate_slot`` output
    (``OpDef.accumulate_grads``)."""
    if accumulate:
        fwd_attrs = dict(fwd_attrs, __accumulate__=tuple(accumulate))
    diff_tree, closed = {}, {}
    for s, args in fwd_ins.items():
        if s in fwd_def.nondiff_inputs or s not in grad_slots:
            closed[s] = args
            continue
        diff_tree[s] = [a if _is_float(a) else None for a in args]
        closed[s] = [None if _is_float(a) else a for a in args]

    def fwd_fn(diff):
        merged = {}
        for s, ca in closed.items():
            da = diff.get(s, [None] * len(ca))
            merged[s] = [d if d is not None else c for d, c in zip(da, ca)]
        outs = fwd_def.fn(merged, fwd_attrs, ctx)
        return {s: [o if _is_float(o) else None for o in v]
                for s, v in outs.items()
                if s not in fwd_def.nondiff_outputs}, outs

    primal_outs, vjp_fn, outs = jax.vjp(fwd_fn, diff_tree, has_aux=True)
    return outs, (primal_outs, vjp_fn)


@register_op("generic_grad", differentiable=False)
def _generic_grad(ins, attrs, ctx):
    """ins:  I_<slot> forward inputs, G_<slot> output cotangents
          (``G_<slot>@ACC``: a partial gradient the backward adds into
          GI_<slot>, for the slots of attr ``accumulate``,
          ``OpDef.accumulate_grads``).
    outs: GI_<slot> input cotangents (only for slots listed in grad_slots).

    Applies the ``jax.vjp`` that ``run_block_ops`` kept when it lowered the
    forward partner (``ctx.kept_vjp``); with none handed over, or for an op
    with ``custom_grad``, traces the forward lowering again over ``I_<slot>``
    (``backward.vjp_kept`` / ``backward.vjp_retraced`` count which).
    """
    fwd_def = get_op(attrs["fwd_type"])
    fwd_attrs = attrs["fwd_attrs"]
    grad_slots: List[str] = attrs["grad_slots"]         # slots needing grads
    kept = getattr(ctx, "kept_vjp", None)
    if kept is not None:
        ctx.kept_vjp = None
    trace.metrics().counter("backward.vjp_retraced" if kept is None
                            else "backward.vjp_kept").inc()

    if kept is None:
        fwd_ins = {s: list(ins.get("I_" + s, [])) for s in attrs["in_slots"]}
        if fwd_def.custom_grad is not None:
            fwd_outs = fwd_def.fn(fwd_ins, fwd_attrs, ctx)
            out_grads = {}
            for s in fwd_outs:
                gs = ins.get("G_" + s)
                out_grads[s] = gs[0] if gs else None
            in_grads = fwd_def.custom_grad(fwd_ins, fwd_outs, out_grads,
                                           fwd_attrs, ctx)
            return {"GI_" + s: v for s, v in in_grads.items()
                    if s in grad_slots}
        _, kept = lower_under_vjp(fwd_def, fwd_ins, fwd_attrs, ctx,
                                  grad_slots, attrs.get("accumulate", ()))

    primal_outs, vjp_fn = kept
    cotangents = {}
    for s, outs_ in primal_outs.items():
        gs = ins.get("G_" + s, [])
        cts = []
        for i, o in enumerate(outs_):
            if o is None:
                cts.append(None)
            elif i < len(gs) and gs[i] is not None:
                cts.append(gs[i].astype(o.dtype)
                           if gs[i].dtype != o.dtype else gs[i])
            else:
                cts.append(jnp.zeros_like(o))
        cotangents[s] = cts
    (in_grads,) = vjp_fn(cotangents)

    result = {}
    for s in grad_slots:
        grads = in_grads.get(s, [])
        result["GI_" + s] = [g if g is not None
                             else jnp.zeros((), jnp.float32) for g in grads]
    return result


def _folded_casts(op, slot, prefix=""):
    """The dtypes ``prune_redundant_casts`` folded onto ``slot``'s args
    (``__amp_cast__``), without the trailing args it left alone."""
    dts = list((op.attrs.get("__amp_cast__") or {}).get(prefix + slot) or ())
    while dts and dts[-1] is None:
        dts.pop()
    return dts


def _same_attrs(fwd_attrs, op_attrs) -> bool:
    """The attrs ``append_backward`` copied are still the forward op's
    (the folded casts are compared per slot, by ``pair_grads``)."""
    try:
        return ({k: v for k, v in fwd_attrs.items() if k != "__amp_cast__"}
                == {k: v for k, v in op_attrs.items() if k != "__amp_cast__"})
    except ValueError:      # an array-valued attr that is another object
        return False


def pair_grads(op_list) -> Dict[int, Any]:
    """``{id(forward op): its generic_grad}`` over one traced op list, read
    from what the ops hold: the grad names the forward's type, its
    ``I_<slot>`` mirrors are the forward's inputs name for name, under the
    same folded AMP casts, its ``fwd_attrs`` are the forward's attrs, and
    it comes later.  One grad per forward; an op with ``custom_grad`` has
    none (its grad needs the forward's outputs, not a vjp).  A
    ``generic_grad`` left out here re-traces its forward."""
    grads: Dict[tuple, List[tuple]] = {}
    for j, g in enumerate(op_list):
        if g.type != "generic_grad":
            continue
        key = (g.attrs["fwd_type"],
               tuple(sorted((s, tuple(g.inputs.get("I_" + s, ())))
                            for s in g.attrs["in_slots"])))
        grads.setdefault(key, []).append((j, g))
    pairs: Dict[int, Any] = {}
    if not grads:
        return pairs
    for i, f in enumerate(op_list):
        if f.type == "generic_grad" or not has_op(f.type) \
                or get_op(f.type).custom_grad is not None:
            continue
        key = (f.type, tuple(sorted((s, tuple(ns))
                                    for s, ns in f.inputs.items())))
        waiting = grads.get(key, ())
        for k, (j, g) in enumerate(waiting):
            if j > i and _same_attrs(g.attrs["fwd_attrs"], f.attrs) \
                    and all(_folded_casts(f, s) == _folded_casts(g, s, "I_")
                            for s in f.inputs):
                pairs[id(f)] = g
                del waiting[k]
                break
    return pairs


# ---------------------------------------------------------------------------
# append_backward
# ---------------------------------------------------------------------------
def _forward_requires(block: Block, targets: Set[str],
                      no_grad: Set[str]) -> Set[str]:
    """Forward propagate 'requires grad' from trainable leaves."""
    req = set()
    for v in block.program.global_block().vars.values():
        if isinstance(v, Parameter) and v.trainable and v.name not in no_grad:
            req.add(v.name)
    for v in block.vars.values():
        if v.is_data and not v.stop_gradient and v.name not in no_grad:
            req.add(v.name)
    for op in block.ops:
        opdef = get_op(op.type) if has_op(op.type) else None
        if opdef is None or not opdef.differentiable:
            continue
        if any(n in req for n in op.input_arg_names):
            for n in op.output_arg_names:
                var = block._find_var_recursive(n)
                if var is None or not var.stop_gradient:
                    req.add(n)
    return req


def _relevant_to(block: Block, loss_name: str) -> Set[str]:
    """Backward reachability: vars that influence the loss."""
    rel = {loss_name}
    for op in reversed(block.ops):
        if any(n in rel for n in op.output_arg_names):
            rel.update(op.input_arg_names)
    return rel


def append_backward(loss: Variable, parameter_list=None,
                    no_grad_set: Optional[Set[str]] = None,
                    callbacks=None, checkpoints=None) -> List:
    """Append grad ops computing d(loss)/d(param) for every trainable param.

    Returns [(param, grad_var)] like the reference (backward.py:1276).
    `checkpoints` (recompute segments) are honored by the executor via
    jax.checkpoint boundaries (see RecomputeOptimizer).
    """
    block = loss.block
    program = block.program
    no_grad = set(no_grad_set or ())
    requires = _forward_requires(block, {loss.name}, no_grad)
    relevant = _relevant_to(block, loss.name)

    # loss cotangent = 1 (fill_constant, like fluid's fill op for loss@GRAD)
    loss_grad = _grad_name(loss.name)
    block.append_op(
        "fill_constant", outputs={"Out": [loss_grad]},
        attrs={"shape": list(loss.shape or ()), "value": 1.0,
               "dtype": loss.dtype or "float32", "op_role": 1})
    block.var(loss_grad).stop_gradient = True

    # var -> list of partial grad var names (summed at the end)
    grads: Dict[str, List[str]] = {loss.name: [loss_grad]}

    fwd_ops = [op for op in block.ops[:-1]]  # exclude the fill we just added
    for op in reversed(fwd_ops):
        if not has_op(op.type) or op.type == "generic_grad":
            continue
        opdef = get_op(op.type)
        if not opdef.differentiable:
            continue
        if not any(n in relevant and n in grads for n in op.output_arg_names):
            continue
        grad_slots = []
        for slot, names in op.inputs.items():
            if slot in opdef.nondiff_inputs:
                continue
            if any(n in requires and n not in no_grad for n in names):
                grad_slots.append(slot)
        if not grad_slots:
            continue

        g_ins: Dict[str, List[str]] = {}
        for slot, names in op.inputs.items():
            g_ins["I_" + slot] = list(names)
        has_any_outgrad = False
        for slot, names in op.outputs.items():
            if slot in opdef.nondiff_outputs:
                continue
            gnames = []
            ok = False
            for n in names:
                if n in grads:
                    gnames.append(_sum_partials(block, n, grads))
                    ok = True
                else:
                    gnames = None
                    break
            if ok and gnames is not None:
                g_ins["G_" + slot] = gnames
                has_any_outgrad = True
        if not has_any_outgrad:
            continue

        into = _accumulating(op, opdef, grad_slots, grads)
        for slot in into:
            g_ins["G_" + accumulate_slot(slot)] = [
                _sum_partials(block, n, grads) for n in op.input(slot)]
        g_outs: Dict[str, List[str]] = {}
        for slot in grad_slots:
            outs = []
            for n in op.input(slot):
                gname = _partial_name(block, n, grads)
                outs.append(gname)
                if slot in into:        # the gradient so far, with this op's
                    grads[n] = [gname]
                else:
                    grads.setdefault(n, []).append(gname)
            g_outs["GI_" + slot] = outs

        g_attrs = {"fwd_type": op.type, "fwd_attrs": dict(op.attrs),
                   "in_slots": list(op.inputs.keys()),
                   "grad_slots": grad_slots, "op_role": 1}
        if into:
            g_attrs["accumulate"] = into
        block.append_op("generic_grad", inputs=g_ins, outputs=g_outs,
                        attrs=g_attrs)
        for slot_outs in g_outs.values():
            for n in slot_outs:
                block.var(n).stop_gradient = True

    # build (param, grad) list
    params = (list(parameter_list) if parameter_list
              else [p for p in program.all_parameters() if p.trainable])
    result = []
    for p in params:
        p_obj = p if isinstance(p, Variable) else block.var(p)
        if p_obj.name in no_grad or p_obj.name not in grads:
            continue
        gname = _sum_partials(block, p_obj.name, grads)
        gvar = block.var(gname)
        gvar.shape = p_obj.shape
        gvar.dtype = p_obj.dtype
        result.append((p_obj, gvar))
    return result


def _accumulating(op, opdef, grad_slots, grads) -> List[str]:
    """The slots of ``op``'s grad that take the partial gradients found so
    far as an operand to add into (``OpDef.accumulate_grads``) instead of
    leaving them to a ``sum``: a slot whose every variable has partials
    already (from the grads of its later readers) and is no other input
    of ``op``."""
    names, written = op.input_arg_names, op.output_arg_names
    return [s for s in opdef.accumulate_grads if s in grad_slots
            and all(n in grads and names.count(n) == 1
                    and n not in written for n in op.input(s))]


def _partial_name(block: Block, name: str, grads: Dict[str, List[str]]) -> str:
    """A new partial gradient's name: ``name@GRAD`` for the first,
    ``name@GRAD@RENAME_<k>`` with ``k`` the partials held, raised past a
    name in use (a partial an accumulating grad op took, or a sum's)."""
    gname = _grad_name(name)
    taken = {x for v in grads.values() for x in v}
    if name not in grads and gname not in taken:
        return gname
    k = len(grads.get(name, []))
    while f"{gname}@RENAME_{k}" in taken \
            or block.has_var(f"{gname}@RENAME_{k}"):
        k += 1
    return f"{gname}@RENAME_{k}"


def _sum_partials(block: Block, name: str, grads: Dict[str, List[str]]) -> str:
    """Collapse accumulated partial grads into one var (fluid's inserted
    `sum` op for fan-in, backward.py _addup_repetitive_outputs_)."""
    parts = grads[name]
    if len(parts) == 1:
        final = parts[0]
    else:
        final = _grad_name(name)
        if final in parts or block.has_var(final):
            tmp = final + "@SUM"
            while block.has_var(tmp):
                tmp += "@SUM"
            block.append_op("sum", inputs={"X": parts},
                            outputs={"Out": [tmp]}, attrs={"op_role": 1})
            final = tmp
        else:
            block.append_op("sum", inputs={"X": parts},
                            outputs={"Out": [final]}, attrs={"op_role": 1})
        block.var(final).stop_gradient = True
    grads[name] = [final]
    return final


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """paddle.static.gradients / fluid calc_gradient (backward.py:1729)."""
    t = targets[0] if isinstance(targets, (list, tuple)) else targets
    pairs = append_backward(t, parameter_list=None, no_grad_set=no_grad_set)
    gmap = {p.name: g for p, g in pairs}
    ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    out = []
    for v in ins:
        gname = _grad_name(v.name)
        out.append(t.block.var(gname) if t.block.has_var(gname) else None)
    return out
