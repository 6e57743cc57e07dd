"""Hybrid-parallel training over a device mesh: dp/pp/tp/sp axes with
XLA collectives over ICI.  Runs on real chips unchanged; this script
demonstrates on 8 VIRTUAL cpu devices so it works anywhere.

Run: python examples/multi_chip.py          (~60s on CPU)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    # 8 virtual devices where the platform is the host CPU (the flag is
    # inert on an accelerator).  APPEND to any preexisting flags:
    # setdefault would silently drop the virtual devices and degrade the
    # demo to a 1-device mesh
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax


def main():
    import numpy as np
    from paddle_tpu.parallel.hybrid import (TransformerConfig,
                                            build_hybrid_mesh,
                                            demo_batch, make_train_step)

    mesh = build_hybrid_mesh(len(jax.devices()))
    ax = {a: mesh.shape[a] for a in mesh.axis_names}
    heads = 2 * ax["tp"]
    cfg = TransformerConfig(vocab=64 * ax["tp"], d_model=16 * heads,
                            n_heads=heads, d_ff=32 * heads,
                            n_layers=2 * ax["pp"], seq_len=16 * ax["sp"],
                            batch=4 * max(1, ax["dp"]), microbatches=2,
                            sp_mode="ring")
    print(f"mesh: {ax} — ring attention over sp, Megatron tp, GPipe pp")
    params, opt_state, step_fn = make_train_step(mesh, cfg)
    tok, lbl = demo_batch(cfg, mesh, seed=0)
    for step in range(3):
        params, opt_state, loss = step_fn(params, opt_state, tok, lbl)
        print(f"step {step}: loss={float(loss):.4f}")
    assert np.isfinite(float(loss))


if __name__ == "__main__":
    main()
