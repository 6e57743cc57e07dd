"""Each configuration's Program against its plain float32 reference, at a
tiny size on the CPU: loss and the gradients of the three checked parameters,
in float32 (exact to rounding) and under the bf16 AMP plane (within the
configuration's tolerance); and the comparison fails what it should."""
import os

import numpy as np
import pytest

from benchmark.harness import compare
from benchmark.harness.registry import Registry, load_module
from benchmark.tests.conftest import TINY_BERT, TINY_RESNET

REG = Registry()


@pytest.fixture(scope="module")
def bert():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.core import Scope, scope_guard
    cfg, cfg_dir = REG.config("bert_base_pretrain")
    cfg.update(TINY_BERT["config"])
    mix = REG.mix("pretrain_seq128")
    mix.update(TINY_BERT["mix"])
    model = load_module(os.path.join(cfg_dir, "model.py"))
    reference = load_module(os.path.join(cfg_dir, "reference.py"))
    kind = REG.module("traffic_kinds", "bert_pretrain.py")
    mix["length"] = {"dist": "uniform", "min": 8}      # padding masks too
    batch = kind.generate(mix, cfg, 3, 4, n_batches=1)[0]
    wanted = cfg["check"]["parameters"]
    train = model.build(cfg, mix, train=True)
    check = model.build(cfg, mix, train=False)
    train["startup"].random_seed = 5
    exe = fluid.Executor()
    out = {"cfg": cfg, "model": model, "mix": mix}
    with scope_guard(Scope()):
        exe.run(train["startup"])
        scope = fluid.global_scope()
        params = {p.name: scope.find_var(p.name)
                  for p in train["main"].all_parameters()}
        out["n_params"] = sum(int(np.prod(v.shape)) for v in params.values())
        out["ref"] = compare.reference_loss_and_grads(
            reference.loss, params, batch, cfg, wanted)
        fetch = [check["loss"].name] + [check["grads"][w] for w in wanted]
        out["f32"] = exe.run(check["main"], feed=batch, fetch_list=fetch)
        bs = fluid.BuildStrategy()
        bs.amp = True
        amp_check = model.build(cfg, mix, train=False)
        out["amp"] = exe.run(fluid.CompiledProgram(
            amp_check["main"], build_strategy=bs), feed=batch,
            fetch_list=[amp_check["loss"].name]
            + [amp_check["grads"][w] for w in wanted])
    out["wanted"] = wanted
    return out


def _compare(bert, got, check):
    ref_loss, ref_grads = bert["ref"]
    return compare.against_reference(
        got[0].ravel()[0], dict(zip(bert["wanted"], got[1:])), ref_loss,
        {k: np.asarray(v) for k, v in ref_grads.items()}, check)


def test_bert_program_is_the_reference_graph_in_float32(bert):
    ok, report = _compare(bert, bert["f32"],
                          {"loss_rel_tol": 1e-5, "grad_rel_l2_tol": 1e-4})
    assert ok, report


def test_bert_under_amp_is_within_the_configurations_tolerance(bert):
    ok, report = _compare(bert, bert["amp"], bert["cfg"]["check"])
    assert ok, report
    # and bf16 is really on: it cannot be as close as float32 is
    assert max(report["grad_rel_l2"].values()) > 1e-3, report


def test_parameter_count_is_the_published_one(bert):
    assert bert["model"].param_count(bert["cfg"]) == bert["n_params"]
    full, _ = REG.config("bert_base_pretrain")
    assert bert["model"].param_count(full) == 110_106_428   # tied decoder


def test_flops_and_bytes_functions():
    cfg, cfg_dir = REG.config("bert_base_pretrain")
    model = load_module(os.path.join(cfg_dir, "model.py"))
    f128 = model.flops_per_sample(cfg, REG.mix("pretrain_seq128"))
    f512 = model.flops_per_sample(cfg, REG.mix("pretrain_seq512"))
    # by hand: 12 x 128 x (8 H^2 + 4 H I + 4 S H) + heads, x 3
    assert f128 == pytest.approx(3 * (12 * 128 * 14_548_992 + 1_179_648
                                      + 3_072 + 20 * (1_179_648
                                                      + 46_881_792)))
    assert 4.2 < f512 / f128 < 4.4       # 4x the tokens, longer attention
    assert model.bytes_per_step(cfg, REG.mix("pretrain_seq128"), 128) \
        == pytest.approx(40 * 110_106_428 + 4 * 12 * 128 * 128 * 9_216)


def test_the_comparison_fails_what_it_should(bert):
    check = bert["cfg"]["check"]
    ref_loss, ref_grads = bert["ref"]
    good = [np.asarray(ref_loss)] + [np.asarray(ref_grads[w])
                                     for w in bert["wanted"]]
    assert _compare(bert, good, check)[0]
    off = [g.copy() for g in good]
    off[2] = off[2] * 1.10                       # one gradient 10% out
    assert not _compare(bert, off, check)[0]
    off = [g.copy() for g in good]
    off[0] = off[0] * (1 + 5e-3)                 # the loss 0.5% out
    assert not _compare(bert, off, check)[0]
    off = [g.copy() for g in good]
    off[1] = np.full_like(off[1], np.nan)
    assert not _compare(bert, off, check)[0]


# -- resnet50 ---------------------------------------------------------------

@pytest.fixture(scope="module")
def resnet():
    import types

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.core import Scope, scope_guard
    cfg, cfg_dir = REG.config("resnet50")
    cfg.update(TINY_RESNET["config"])
    mix = REG.mix("imagenet_b256")
    mix.update(TINY_RESNET["mix"])
    model = load_module(os.path.join(cfg_dir, "model.py"))
    reference = load_module(os.path.join(cfg_dir, "reference.py"))
    kind = REG.module("traffic_kinds", "image_classification.py")
    batch = kind.generate(mix, cfg, 3, 4, n_batches=1)[0]
    train = model.build(cfg, mix, train=True)
    train["startup"].random_seed = 5
    exe = fluid.Executor()
    out = {"cfg": cfg, "model": model}

    def amp(built):
        bs = fluid.BuildStrategy()
        bs.amp = True
        return fluid.CompiledProgram(built["main"], build_strategy=bs)

    def flipped(name):
        """A reference whose convolution ``name`` is turned upside down: what
        a program that computes that convolution wrongly looks like."""
        return types.SimpleNamespace(loss=lambda p, b, c: reference.loss(
            {**p, name: p[name][:, :, ::-1, ::-1]}, b, c))

    def run(prepare, ref=reference, **check):
        c = {**cfg, "check": {**cfg["check"], **check}}
        return compare.program_against_reference(exe, prepare, model, ref, c,
                                                 mix, batch)

    with scope_guard(Scope()):
        exe.run(train["startup"])
        scope = fluid.global_scope()
        out["n_params"] = sum(
            int(np.prod(p.shape)) for p in train["main"].all_parameters()
            if p.trainable)
        exact = {"loss_rel_tol": 1e-5, "grad_rel_l2_tol": 1e-3}
        out["f32"] = run(lambda built: built["main"], **exact)
        out["amp"] = run(amp)
        out["flipped"] = {
            name: run(lambda built: built["main"], ref=flipped(name))
            for name in ("stage_1.block_1.conv2.w", "stage_3.block_0.conv2.w")}
        out["as_initialised"] = run(
            lambda built: built["main"],
            ref=flipped("stage_1.block_1.conv2.w"),
            set_parameters={"stage_*.conv3.bn.scale": 0.0},
            parameters=["conv1.w", "stage_1.block_0.shortcut.w", "fc.w"])
        out["scale_after"] = np.asarray(
            scope.find_var("stage_1.block_1.conv3.bn.scale"))
    return out


def test_resnet_program_is_the_reference_graph_in_float32(resnet):
    ok, report = resnet["f32"]
    assert ok, report


def test_resnet_under_amp_is_within_its_tolerance(resnet):
    ok, report = resnet["amp"]
    assert ok, report
    assert max(report["grad_rel_l2"].values()) > 1e-3, report


def test_resnet_check_sees_inside_the_bottleneck_branches(resnet):
    # one convolution of one branch computed wrongly, everything else exact:
    # the configuration's own tolerances have to refuse it
    for name, (ok, report) in resnet["flipped"].items():
        assert not ok, (name, report)
    # with the last BatchNorm of every block at scale 0 a branch's output is
    # exactly 0 and the same fault is invisible to every gradient outside the
    # branches: why the check sets weights of its own and looks inside
    ok, report = resnet["as_initialised"]
    assert ok, report
    # the check's own weights are gone from the scope afterwards
    assert np.all(resnet["scale_after"] == 1.0)


def test_resnet_counts_are_the_published_ones(resnet):
    assert resnet["model"].param_count(resnet["cfg"]) == resnet["n_params"]
    full, _ = REG.config("resnet50")
    model = resnet["model"]
    assert model.param_count(full) == 25_557_032
    mix = REG.mix("imagenet_b256")
    # 4.09 G multiply-adds forward (torchvision's net, "v1.5"), x 2 x 3
    assert model.flops_per_sample(full, mix) == pytest.approx(
        3 * 8.178e9, rel=1e-3)
    convs, c_last = model.conv_shapes(full)
    assert len(convs) == 53 and c_last == 2048 and convs[-1][-1] == 7
