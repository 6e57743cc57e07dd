"""``reference.py`` with one term wrong at a time: what the configuration's
limits have to catch (``benchmark/tools/check_seeds.py --faults 1`` on the
chip, ``tests/test_phi4_flash_program.py`` at a small size).  ``planted(name)``
gives a copy of the reference module of its own with that one helper
replaced; ``reference.py`` itself knows nothing of this file, and the module
the harness loads for ``correct`` is never touched.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

_REFERENCE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "reference.py")


def _bf16_state(ref):
    step = ref._step
    ref._step = lambda state, tok, a, d: step(
        state.astype(jnp.bfloat16).astype(jnp.float32), tok, a, d)


def _a_sign(ref):
    scan = ref._scan
    ref._scan = lambda xc, dt, a, b, c, d: scan(xc, dt, -a, b, c, d)


def _no_softplus(ref):
    ref._step_size = lambda dl, w: dl @ w["ssm.dt_proj.w"] \
        + w["ssm.dt_proj.b"]


def _no_skip(ref):
    scan = ref._scan
    ref._scan = lambda xc, dt, a, b, c, d: scan(xc, dt, a, b, c,
                                                jnp.zeros_like(d))


def _conv_sees_next(ref):
    conv = ref._conv
    ref._conv = lambda xs, w: conv(
        jnp.pad(xs[:, 1:], ((0, 0), (0, 1), (0, 0))), w)


def _memory_gated(ref):
    mamba = ref._mamba

    def gated(u, w, cfg):
        out, y = mamba(u, w, cfg)
        z = (u @ w["ssm.in_proj.w"])[..., y.shape[-1]:]
        return out, y * jax.nn.silu(z)
    ref._mamba = gated


def _one_lambda_init(ref):
    init = ref._lambda_init
    ref._lambda_init = lambda i: init(0)


def _no_subln(ref):
    ref._subln = lambda d, g, lam0, eps: d * (1.0 - lam0)


def _no_one_minus_lambda(ref):
    subln = ref._subln
    ref._subln = lambda d, g, lam0, eps: subln(d, g, 0.0, eps)


def _window_ignored(ref):
    kind = ref._kind
    ref._kind = lambda cfg, i: (kind(cfg, i)[0], 0)


def _first_layers_keys(ref):
    kind = ref._kind
    ref._kv_layer = lambda cfg: next(
        i for i in cfg["held_layers"] if kind(cfg, i)[0] == "attention")


def _untied_head(ref):
    summed = ref._summed_loss
    ref._summed_loss = lambda h, labels, head: summed(
        h, labels, jax.lax.stop_gradient(head))


FAULTS = {
    "bf16_state": _bf16_state,            # the scan's state kept in bfloat16
    "a_sign": _a_sign,                    # A = exp(A_log), no minus
    "no_softplus": _no_softplus,          # dt = W_dt dl + b_dt
    "no_skip": _no_skip,                  # y without D xc
    "conv_sees_next": _conv_sees_next,    # token t's taps on t - 2 .. t + 1
    "memory_gated": _memory_gated,        # the GMU reads y silu(z)
    "one_lambda_init": _one_lambda_init,  # lam0 of layer 0 on every layer
    "no_subln": _no_subln,
    "no_one_minus_lambda": _no_one_minus_lambda,
    "window_ignored": _window_ignored,    # every attention layer full causal
    "first_layers_keys": _first_layers_keys,  # cross reads layer 1's k, v
    "untied_head": _untied_head,          # no gradient from the head to E
}


def planted(name):
    """A fresh copy of the reference module with fault ``name`` in it."""
    spec = importlib.util.spec_from_file_location(
        "phi4_flash_reference_" + name, _REFERENCE)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    FAULTS[name](ref)
    return ref
