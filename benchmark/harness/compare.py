"""The comparison that decides ``correct``: the system's loss and gradients
against those of the configuration's plain float32 reference, under the
tolerances the configuration's ``check`` states with their reason."""
from __future__ import annotations

import fnmatch
import math

import numpy as np


def rel_err(got, want):
    got, want = float(got), float(want)
    return abs(got - want) / max(abs(want), 1e-30)


def rel_l2(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def reference_loss_and_grads(loss_fn, params, batch, cfg, wanted):
    """(loss, {name: d loss / d params[name] for name in wanted}) of a
    configuration's ``reference.loss(params, batch, cfg)``, in float32 at the
    highest matmul and convolution precision."""
    import jax

    def f(sub, rest, feeds):
        return loss_fn({**rest, **sub}, feeds, cfg)

    # weights and batch are arguments, never constants of the executable:
    # one compile-cache entry serves every seed
    sub = {n: params[n] for n in wanted}
    rest = {n: v for n, v in params.items() if n not in sub}
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(f))(sub, rest, dict(batch))


def program_against_reference(exe, prepare, model, reference, cfg, mix, batch):
    """(ok, report) of the configuration's dropout-free Program, as
    ``prepare(built)`` makes it runnable (the cell's ``CompiledProgram``),
    against ``reference.loss``, both on ``batch`` and on the weights in the
    current scope.  ``check["set_parameters"]`` (glob over parameter names ->
    value) fills the parameters it names with that value for the comparison
    alone: where the weights a job starts from hide a part of the graph or
    make it chaotic, the check chooses its own.  The scope is left as it was."""
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid

    check = cfg["check"]
    wanted = list(check["parameters"])
    built = model.build(cfg, mix, train=False)
    names = [p.name for p in built["main"].all_parameters()]
    scope = fluid.global_scope()
    saved = {}
    for pattern, value in check.get("set_parameters", {}).items():
        hit = fnmatch.filter(names, pattern)
        if not hit:
            raise KeyError(f"check.set_parameters: no parameter matches "
                           f"{pattern!r}")
        for name in hit:
            saved[name] = scope.find_var(name)
            scope.set_var(name, jnp.full_like(saved[name], value))
    try:
        params = {name: scope.find_var(name) for name in names}
        ref_loss, ref_grads = reference_loss_and_grads(
            reference.loss, params, batch, cfg, wanted)
        got = exe.run(prepare(built), feed=batch,
                      fetch_list=[built["loss"].name]
                      + [built["grads"][w] for w in wanted])
    finally:
        for name, value in saved.items():
            scope.set_var(name, value)
    return against_reference(
        np.asarray(got[0]).ravel()[0], dict(zip(wanted, got[1:])), ref_loss,
        {w: np.asarray(g) for w, g in ref_grads.items()}, check)


def against_reference(loss, grads, ref_loss, ref_grads, check):
    """(ok, report).  ``grads`` and ``ref_grads`` map parameter name -> array;
    ``check`` holds ``loss_rel_tol`` and ``grad_rel_l2_tol``, one number for
    all parameters or one per parameter name.  A non-finite value or a
    reference gradient that is identically zero never passes."""
    report = {"loss": float(loss), "ref_loss": float(ref_loss),
              "loss_rel_err": rel_err(loss, ref_loss), "grad_rel_l2": {}}
    ok = math.isfinite(report["loss_rel_err"]) \
        and report["loss_rel_err"] <= check["loss_rel_tol"]
    tols = check["grad_rel_l2_tol"]
    for name, want in ref_grads.items():
        err = rel_l2(grads[name], want)
        report["grad_rel_l2"][name] = err
        tol = tols[name] if isinstance(tols, dict) else tols
        ok = ok and math.isfinite(err) and err <= tol \
            and float(np.abs(np.asarray(want)).max()) > 0.0
    return bool(ok), report
