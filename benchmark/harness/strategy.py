"""A cell's ``BuildStrategy``: the configuration's ``build_strategy`` fields
(its precision plane) and the mix's ``layout`` fields (how the job is divided
over the chips), set by name.  Everything else stays at the program's default:
no cell sets a speed option."""
from __future__ import annotations


def build_strategy(cfg, mix):
    import paddle_tpu.fluid as fluid
    bs = fluid.BuildStrategy()
    for key, value in {**cfg.get("build_strategy", {}),
                       **mix.get("layout", {})}.items():
        if not hasattr(bs, key):
            raise KeyError(f"BuildStrategy has no field {key!r}")
        setattr(bs, key, value)
    return bs
