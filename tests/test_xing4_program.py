"""The Xing4.0 configuration of the benchmark (benchmark/configs/
xing4_29b_a4b_train) through Program -> passes -> Executor, at a small size
on the CPU: against its float32 reference with and without the MTP module,
under AMP, its counts, the shares of the eight chips added up, the
device-side gauges, and its four readers on a hand-made trace."""
import fnmatch
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace
from paddle_tpu.fluid.core import Scope, scope_guard
from paddle_tpu.fluid.framework import reset_unique_name
from paddle_tpu.ops.registry import get_op
from paddle_tpu.parallel import moe

from benchmark.harness import compare, program_ops
from benchmark.harness.registry import Registry, load_module
from benchmark.harness.spans import Spans
from benchmark.harness.strategy import build_strategy

REG = Registry()
CONFIG, CELL = "xing4_29b_a4b_train", "xing4_train_seq4096"
# every number shrunk, the graph kept: a dense layer and two expert layers,
# four heads that score over 8 + 4 numbers and carry values of 8, latents of
# 16 and 12, 4 of 16 experts held (the second share), top-4, one shared
SMALL = {"hidden_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 4, "qk_nope_head_dim": 8,
         "qk_rope_head_dim": 4, "v_head_dim": 8, "q_lora_rank": 16,
         "kv_lora_rank": 12, "intermediate_size": 48,
         "moe_intermediate_size": 16, "vocab_size": 128,
         "n_routed_experts": 4, "num_hidden_layers": 3, "first_expert": 4,
         "published": {"num_hidden_layers": 40, "first_k_dense_replace": 2,
                       "n_routed_experts": 16, "vocab_size": 1024,
                       "num_nextn_predict_layers": 1}}
MIX = {"seq_len": 16, "samples_per_chip": 2}
# the mixers as the chip's check sets them: every gate and the whole mixing
# matrix depend on the token
MIXING = {"*.hc.alpha": 1.0, "*.hc.b": 0.0}


def _load():
    cfg, cfg_dir = REG.config(CONFIG)
    mix = REG.mix(REG.cell(CELL)["traffic"])
    return (cfg, mix, load_module(os.path.join(cfg_dir, "model.py")),
            load_module(os.path.join(cfg_dir, "reference.py")))


def _small(mtp=0):
    cfg, mix, model, reference = _load()
    cfg.update(SMALL, num_nextn_predict_layers=mtp)
    mix.update(MIX)
    return cfg, mix, model, reference


def _against_reference(amp, mtp=0, seed=11):
    """Loss and EVERY parameter's gradient against the reference."""
    cfg, mix, model, reference = _small(mtp)
    cfg["build_strategy"] = {"amp": amp}
    kind = REG.module("traffic_kinds", mix["kind"] + ".py")
    batch = kind.generate(mix, cfg, seed, 2, n_batches=1)[0]
    reset_unique_name()
    train = model.build(cfg, mix, train=True)
    train["startup"].random_seed = seed
    cfg["check"] = {
        "samples": 2, "set_parameters": MIXING, "loss_rel_tol": 1.0,
        "grad_rel_l2_tol": 1.0,
        "parameters": [p.name for p in train["main"].all_parameters()
                       if p.trainable]}
    exe = fluid.Executor()

    def compiled(built):
        return fluid.CompiledProgram(
            built["main"], build_strategy=build_strategy(cfg, mix))
    with scope_guard(Scope()):
        exe.run(train["startup"])
        ok, report = compare.program_against_reference(
            exe, compiled, model, reference, cfg, mix, batch)
    assert ok, report
    return report


@pytest.mark.parametrize("mtp", [0, 1])
def test_program_equals_reference_in_float32(mtp):
    report = _against_reference(amp=False, mtp=mtp)
    assert report["loss_rel_err"] < 1e-5
    assert len(report["grad_rel_l2"]) == (90 if mtp else 65)
    assert any(n.startswith("mtp.") for n in report["grad_rel_l2"]) == bool(mtp)
    # the 20 normalisations in float32 on both sides: the mixers' own
    # parameters agree to a few 1e-3, everything else far closer
    for name, err in report["grad_rel_l2"].items():
        assert err < (1e-2 if ".hc." in name else 1e-3), (name, report)


def test_program_under_amp_is_close_and_not_as_close_as_float32():
    exact = _against_reference(amp=False)
    amp = _against_reference(amp=True)
    assert amp["loss_rel_err"] < 2e-3, amp
    worse = 0
    for name, err in amp["grad_rel_l2"].items():
        # 32 tokens: one routing swap is a large share of a router's or an
        # expert's gradient
        loose = "router" in name or "experts" in name
        assert err < (0.8 if loose else 0.15), (name, amp)
        worse += err > 5 * exact["grad_rel_l2"][name]
    assert worse > 0.8 * len(amp["grad_rel_l2"])


@pytest.mark.parametrize("mtp", [0, 1])
def test_the_comparisons_program_is_the_timed_steps_twin(mtp):
    """What decides ``correct`` is the timed path: the comparison's program
    is the training program's forward and ``generic_grad`` chain op for op
    (types, inputs, outputs, attributes), without its optimizer ops and
    with no recompute hint, so a wrong grad lowering fails the comparison."""
    cfg, mix, model, _ = _small(mtp)

    def ops(train):
        reset_unique_name()
        built = model.build(cfg, mix, train=train)
        assert not built["main"]._hints.get("recompute_checkpoints")
        return [(op.type, sorted(op.inputs.items()),
                 sorted(op.outputs.items()),
                 sorted((k, str(v)) for k, v in op.attrs.items()))
                for op in built["main"].global_block().ops]
    step, check = ops(True), ops(False)
    assert sum(t == "generic_grad" for t, *_ in check) > 50
    assert step[:len(check)] == check
    rest = {t for t, *_ in step[len(check):]}
    assert "adam" in rest and "generic_grad" not in rest


def _mixer_fold(mtp, fold):
    """The comparison's program (forward and backward) of the small
    configuration, the mixers' grads taking the streams' other partial
    gradients as their op declares (``fold``) or, with the declaration
    taken away, leaving them to a ``sum``: the loss and every parameter's
    gradient of one run, the vjp counters it moved, and the program."""
    cfg, mix, model, _ = _small(mtp)
    kind = REG.module("traffic_kinds", mix["kind"] + ".py")
    batch = kind.generate(mix, cfg, 11, 2, n_batches=1)[0]
    with pytest.MonkeyPatch.context() as mp:
        if not fold:
            mp.setattr(get_op("hyper_connection_mix"), "accumulate_grads", ())
        reset_unique_name()
        built = model.build(cfg, mix, train=False)
    built["startup"].random_seed = 11
    names = sorted(built["grads"])
    counters = [trace.metrics().counter("backward.vjp_" + k)
                for k in ("kept", "retraced")]
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(built["startup"])
        scope = fluid.global_scope()
        for pattern, value in MIXING.items():
            for name in fnmatch.filter(names, pattern):
                scope.set_var(name, jnp.full_like(scope.find_var(name),
                                                  value))
        before = [c.value for c in counters]
        vals = exe.run(built["main"], feed=batch,
                       fetch_list=[built["loss"].name]
                       + [built["grads"][n] for n in names])
        moved = [c.value - b for c, b in zip(counters, before)]
    exe.close()
    return (dict(zip(["loss"] + names, map(np.asarray, vals))), moved,
            built["main"].global_block())


def _sums_of_mixer_grads(block):
    made = {n for op in block.ops if op.type == "generic_grad"
            and op.attrs["fwd_type"] == "hyper_connection_mix"
            for n in op.output("GI_X")}
    return [op for op in block.ops
            if op.type == "sum" and made & set(op.input("X"))]


@pytest.mark.parametrize("mtp", [0, 1])
def test_mixer_grads_take_the_streams_other_partials(mtp):
    """Each stream feeds its mixer and its merge: the merge's partial
    gradient rides into the mixer's grad, and no ``sum`` adds a mixer
    grad's output; the gradients are the unfolded spelling's, and the
    forward is still lowered once (the vjp counters are the same)."""
    got, moved, block = _mixer_fold(mtp, fold=True)
    want, moved_want, plain = _mixer_fold(mtp, fold=False)
    mixers = sum(op.type == "hyper_connection_mix" for op in block.ops)
    assert mixers == (8 if mtp else 6)
    assert len(_sums_of_mixer_grads(plain)) == mixers
    assert _sums_of_mixer_grads(block) == []
    count = [sum(op.type == "sum" for op in b.ops) for b in (plain, block)]
    assert count[0] - count[1] == mixers
    assert all("G_X@ACC" in op.inputs and op.attrs["accumulate"] == ["X"]
               for op in block.ops if op.type == "generic_grad"
               and op.attrs["fwd_type"] == "hyper_connection_mix")
    # the ops with a custom_grad re-trace, the rest keep, as before
    assert moved == moved_want and moved[0] > 100
    assert got.keys() == want.keys() and len(got) == (91 if mtp else 66)
    for name, w in want.items():
        # float32 adds of the same partials in another order, carried
        # through the layers below
        err = np.linalg.norm(got[name] - w) / np.linalg.norm(w)
        assert err < 1e-5, (name, err)


def test_amp_keeps_mixers_norms_router_and_loss_in_float32():
    cfg, mix, model, _ = _small()
    cfg["build_strategy"] = {"amp": True}
    reset_unique_name()
    built = model.build(cfg, mix, train=True)
    prog = fluid.CompiledProgram(built["main"],
                                 build_strategy=build_strategy(cfg, mix))
    prog._apply_ir_passes([built["loss"].name])
    block = built["main"].global_block()
    casts, types = {}, []
    for op in block.ops:
        if op.attrs.get("op_role", 0):
            continue
        types.append(op.type)
        casts.setdefault(op.type, []).append(
            op.attrs.get("__amp_cast__") or {})

    def only_float32(op_type):
        return all(set(dts) <= {"float32"} for c in casts[op_type]
                   for dts in c.values())
    assert only_float32("hyper_connection_mix")
    assert only_float32("hyper_connection_merge")
    assert only_float32("rms_norm") and only_float32("moe_route")
    assert all(c.get("W") == ["bfloat16"]
               for c in casts["moe_grouped_matmul"])
    assert all(set(c.get("Logits", [])) <= {"float32"}
               for c in casts["softmax_with_cross_entropy"])
    # a dense and two expert layers: 3 attentions, 6 mixers, 2 routers
    assert types.count("fused_multihead_attention") == 3
    assert types.count("hyper_connection_mix") == 6
    assert types.count("hyper_connection_merge") == 6
    assert types.count("moe_route") == types.count("moe_combine") == 2
    assert types.count("rotary_embedding") == 6
    for op in block.ops:
        if op.type == "fused_multihead_attention":
            assert op.attrs["causal"] and op.attrs["window"] == 0
            assert op.attrs["scale"] == pytest.approx(
                12 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
        if op.type == "moe_route":
            assert op.attrs["scoring"] == "sigmoid"
            assert op.attrs["routed_scaling_factor"] == 2.0
            assert (op.attrs["top_k"], op.attrs["first_expert"],
                    op.attrs["num_held"]) == (4, 4, 4)
            assert op.inputs["CorrectionBias"] == [
                op.inputs["RouterWeight"][0][:-1] + "bias"]


def test_param_count_and_required_work():
    cfg, mix, model, _ = _load()
    assert model.param_count(cfg) == 759_346_446
    assert model.param_count(dict(cfg, num_nextn_predict_layers=1)) \
        == 913_470_084
    uncut = dict(cfg, **cfg["published"])
    assert model.param_count(uncut) == 30_276_191_590
    assert model.param_count(dict(uncut, num_nextn_predict_layers=0)) \
        == 29_505_505_264
    for mtp in (0, 1):
        small, small_mix, _, _ = _small(mtp)
        reset_unique_name()
        built = model.build(small, small_mix, train=True)
        counted = sum(int(np.prod(p.shape))
                      for p in built["main"].all_parameters())
        assert counted == model.param_count(small)
    # the issue's reckoning of a step's required work
    forward = model.flops_per_sample(cfg, mix) / 3 / mix["seq_len"]
    assert abs(forward - 950.3e6) < 0.2e6
    attention = model.attention_flops_per_sample(cfg, mix) / 4096
    assert abs(attention / forward - 0.2207) < 0.001
    flops, nbytes = model.hyper_connection_flops_and_bytes_per_sample(cfg,
                                                                      mix)
    assert abs(flops / 3 / 4096 / forward - 0.0072) < 0.0002
    assert 11.7e9 < nbytes < 11.8e9
    assert model.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    assert model.rotary_cos_sin_factor(cfg) == 1.0


def test_config_states_its_cut():
    cfg, mix, _, _ = _load()
    entry = REG._entry("configs", CONFIG)
    assert cfg["source"] == entry["source"] \
        == ("https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
            "config.json")
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert cfg["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
    assert [cfg[k] for k in cfg["reduced"]] == [5, 1, 8, 16384, 0]
    assert "eight chips" in cfg["deployment"]
    for width, value in {
            "hidden_size": 3584, "intermediate_size": 9216,
            "moe_intermediate_size": 1024, "num_attention_heads": 32,
            "num_key_value_heads": 32, "q_lora_rank": 768,
            "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128,
            "num_experts_per_tok": 4, "n_shared_experts": 1, "hc_mult": 4,
            "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
            "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
            "routed_scaling_factor": 2, "rms_norm_eps": 1e-6,
            "rope_theta": 10000, "max_position_embeddings": 262144}.items():
        assert cfg[width] == value, width
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    for key in ("stream_start", "stream_end", "sinkhorn_order", "hc_eps",
                "hc_init", "rotary", "correction_bias", "mtp", "optimizer",
                "initializer_range"):
        assert key in cfg["assumed"], key
    assert cfg["build_strategy"] == {"amp": True}
    assert len(cfg["check"]["parameters"]) >= 6 and cfg["check"]["why"]
    assert (mix["kind"], mix["seq_len"], mix["samples_per_chip"],
            mix["distinct_batches"], mix["layout"]) == (
                "causal_lm", 4096, 1, 8, {})
    cell = REG.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "causal_lm_seq4096", 1)


def test_the_shares_add_up_to_the_uncut_layer():
    """The expert layer of each of the eight chips, the shared expert
    counted once, against the reference's layer given all the experts."""
    _, _, _, reference = _load()
    t, d, f, experts, held = 48, 32, 16, 64, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 9)

    def draw(key, *shape):
        return jax.random.normal(key, shape, jnp.float32) / shape[-2] ** 0.5
    x = jax.random.normal(keys[0], (1, t, d), jnp.float32)
    w = {"router.w": draw(keys[1], d, experts),
         "router.bias": 0.1 * jax.random.normal(keys[2], (experts,)),
         "experts.gate": draw(keys[3], experts, d, f),
         "experts.up": draw(keys[4], experts, d, f),
         "experts.down": draw(keys[5], experts, f, d),
         "shared.gate.w": draw(keys[6], d, f),
         "shared.up.w": draw(keys[7], d, f),
         "shared.down.w": draw(keys[8], f, d)}
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 2,
           "first_expert": 0, "n_shared_experts": 1}
    from paddle_tpu.fluid.param_attr import ParamAttr

    def share(chip):
        """The layer as chip ``chip`` runs it: ``fluid.layers.expert_layer``
        told its experts, the shared expert beside them."""
        reset_unique_name()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            tokens = fluid.data("tokens", [t, d], dtype="float32")
            out = fluid.layers.expert_layer(
                tokens, experts, 4, f, first_expert=chip * held,
                num_held=held, scoring="sigmoid", routed_scaling_factor=2.0,
                router_attr=ParamAttr(name="router.w"),
                correction_bias_attr=ParamAttr(name="router.bias"),
                gate_attr=ParamAttr(name="experts.gate"),
                up_attr=ParamAttr(name="experts.up"),
                down_attr=ParamAttr(name="experts.down"), shared_size=f,
                shared_gate_attr=ParamAttr(name="shared.gate.w"),
                shared_up_attr=ParamAttr(name="shared.up.w"),
                shared_down_attr=ParamAttr(name="shared.down.w"))
        rows = slice(chip * held, (chip + 1) * held)
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            for name, value in w.items():
                fluid.global_scope().set_var(
                    name, value[rows] if name.startswith("experts.")
                    else value)
            got, = exe.run(main, feed={"tokens": np.asarray(x[0])},
                           fetch_list=[out])
        exe.close()
        return np.asarray(got)

    with jax.default_matmul_precision("highest"):
        whole = reference._experts(x, w, cfg)
        shares = [share(chip) for chip in range(experts // held)]
        once = (jax.nn.silu(x[0] @ w["shared.gate.w"])
                * (x[0] @ w["shared.up.w"])) @ w["shared.down.w"]
    # every share holds the shared expert whole: it is counted once
    total = sum(shares) - (len(shares) - 1) * np.asarray(once)
    np.testing.assert_allclose(total, whole[0], rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(once))) > 0.05
    # and each share differs from the others: the experts are not repeated
    assert float(np.max(np.abs(shares[0] - shares[1]))) > 0.05


def test_gauges_leave_the_device_when_the_runner_drains():
    from paddle_tpu.fluid.async_pipeline import AsyncStepRunner
    cfg, mix, model, _ = _small()
    kind = REG.module("traffic_kinds", mix["kind"] + ".py")
    feeds = kind.generate(mix, cfg, 5, 2, n_batches=2)
    reset_unique_name()
    built = model.build(cfg, mix, train=True)
    counters = built["main"]._hints["device_counters"]
    assert counters["layer_0.attn.res_row_sum_error"] \
        == "hc.layer_0.attn.res_row_sum_error"
    assert counters["layer_2.ffn.res_row_sum_error"] \
        == "hc.layer_2.ffn.res_row_sum_error"
    assert counters["layer_1.moe.tokens_per_expert"] \
        == "moe.layer_1.moe.tokens_per_expert"
    assert "layer_0.moe.steps" not in counters          # the dense layer
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(built["startup"])
        runner = AsyncStepRunner(exe, built["main"], [built["loss"]])
        for feed in feeds:
            runner.submit(feed)
        runner.drain()
        for i in range(3):
            for branch in ("attn", "ffn"):
                err = trace.gauge_value(
                    f"hc.layer_{i}.{branch}.res_row_sum_error", -1.0)
                # the start is the plain residual: C = I to 1e-3, and 20
                # iterations leave its rows within 1e-4 of one
                assert 0.0 <= err < 1e-4, (i, branch, err)
        assert trace.gauge_value("moe.layer_1.moe.steps", -1.0) == 2
        counts = [trace.gauge_value(f"moe.layer_2.moe.tokens_per_expert.{e}",
                                    -1.0) for e in range(4)]
        assert 0 < sum(counts) <= 2 * 32 * 4
    exe.close()


def test_attention_lowering_is_counted_once_a_layer():
    cfg, mix, model, _ = _small()
    reset_unique_name()
    built = model.build(cfg, mix, train=True)
    exe = fluid.Executor()
    counter = trace.metrics().counter("attention.lowering.xla")
    kept = trace.metrics().counter("backward.vjp_kept")
    feed = REG.module("traffic_kinds", mix["kind"] + ".py").generate(
        mix, cfg, 5, 2, n_batches=1)[0]
    with scope_guard(Scope()):
        exe.run(built["startup"])
        before, kept_before = counter.value, kept.value
        exe.run(built["main"], feed=feed, fetch_list=[built["loss"]])
        # the CPU takes the XLA path; the grad op applies the kept vjp, so
        # each of the three attentions is lowered once
        assert counter.value - before == 3
        # every mixer op's backward applies the vjp its forward kept
        assert kept.value - kept_before >= 12 + 3
    exe.close()


# ---------------------------------------------------------------------------
# the readers on a hand-made trace
# ---------------------------------------------------------------------------

_T = "f32[8,128]{1,0:T(8,128)}"
_HLO = f"""HloModule jit_fn, is_scheduled=true

ENTRY %main.1 (Arg_0.1: f32[8,128]) -> f32[8,128] {{
  %Arg_0.1 = {_T} parameter(0), metadata={{op_name="feeds['x']"}}
  %mix.1 = {_T} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation, metadata={{op_name="jit(fn)/pd:f:hyper_connection_mix:layer_0.attn.tmp_0/mul"}}
  %qa.2 = {_T} convolution(%mix.1, %mix.1), dim_labels=bf_io->bf, metadata={{op_name="jit(fn)/pd:f:mul:layer_0.attention.q_a.tmp_0/dot_general"}}
  %kernel.3 = {_T} custom-call(%qa.2), custom_call_target="tpu_custom_call", metadata={{op_name="jit(fn)/pd:f:fused_multihead_attention:layer_0.attention.kernel.tmp_0/pallas_call"}}
  %merge.4 = {_T} fusion(%kernel.3), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(fn)/pd:f:hyper_connection_merge:layer_0.attn.tmp_3/add"}}
  %ffn.5 = {_T} convolution(%merge.4, %merge.4), dim_labels=bf_io->bf, metadata={{op_name="jit(fn)/pd:f:mul:fc_0.tmp_0/dot_general"}}
  %route.11 = {_T} fusion(%ffn.5), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="jit(fn)/pd:f:moe_route:layer_1.moe.tmp_0/top_k"}}
  %shared.12 = {_T} convolution(%route.11, %route.11), dim_labels=bf_io->bf, metadata={{op_name="jit(fn)/pd:f:mul:layer_1.moe.shared.gate.tmp_0/dot_general"}}
  %gmm_grad.13 = {_T} custom-call(%shared.12), custom_call_target="tpu_custom_call", metadata={{op_name="jit(fn)/pd:b:moe_grouped_matmul_grad:layer_1.moe.tmp_4.GRAD/transpose(jvp())/pallas_call"}}
  %dispatch_grad.14 = {_T} fusion(%gmm_grad.13), kind=kLoop, calls=%fused_computation.6, metadata={{op_name="jit(fn)/pd:b:moe_dispatch_grad:layer_1.moe.tokens.tmp_0.GRAD/transpose(jvp())/gather"}}
  %merge_grad.6 = {_T} fusion(%dispatch_grad.14), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="jit(fn)/pd:b:hyper_connection_merge_grad:layer_0.attn.tmp_3.GRAD/transpose(jvp())/mul"}}
  %kernel_grad.7 = {_T} custom-call(%merge_grad.6), custom_call_target="tpu_custom_call", metadata={{op_name="jit(fn)/pd:b:fused_multihead_attention_grad:layer_0.attention.q.tmp_0.GRAD/transpose(jvp())/pallas_call"}}
  %qa_grad.8 = {_T} convolution(%kernel_grad.7, %kernel_grad.7), dim_labels=bf_io->bf, metadata={{op_name="jit(fn)/pd:b:mul_grad:layer_0.attn.norm.tmp_0.GRAD/transpose(jvp())/dot_general"}}
  %norm_grad.9 = {_T} fusion(%qa_grad.8), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="jit(fn)/pd:b:rms_norm_grad:layer_0.attn.tmp_0.GRAD/transpose(jvp())/mul"}}
  ROOT %mix_grad.10 = {_T} fusion(%norm_grad.9), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="jit(fn)/pd:b:hyper_connection_mix_grad:embedding_0.tmp_0.GRAD/transpose(jvp())/mul"}}
}}
"""
# instruction -> microseconds on the one device, one after the other
_US = {"mix.1": 40, "qa.2": 10, "kernel.3": 100, "merge.4": 30, "ffn.5": 50,
       "merge_grad.6": 60, "kernel_grad.7": 200, "qa_grad.8": 20,
       "norm_grad.9": 5, "mix_grad.10": 70, "route.11": 7, "shared.12": 11,
       "gmm_grad.13": 13, "dispatch_grad.14": 9}


def _trace_textproto():
    events, metadata, at = [], [], 100
    for i, (name, us) in enumerate(_US.items(), start=2):
        events.append(f"events {{ metadata_id: {i} offset_ps: {at}000000 "
                      f"duration_ps: {us}000000 }}")
        metadata.append(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                        f'"%{name} = {_T} fusion()" }} }}')
        at += us
    return f"""planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 100000000 duration_ps: {at - 100}000000 }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {' '.join(events)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_fn(1)" }} }}
  {' '.join(metadata)}
}}
"""


@pytest.fixture
def reader_ctx(tmp_path, monkeypatch):
    from jax.profiler import ProfileData
    from paddle_tpu.fluid import device_stats
    d = tmp_path / "plugins" / "profile" / "2026_10_02"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_trace_textproto()))
    monkeypatch.setattr(program_ops, "trace_dir", lambda ctx: str(tmp_path))
    monkeypatch.setattr(device_stats, "op_maps", lambda: [
        {"label": "step", "module": "jit_fn",
         "map": device_stats.hlo_op_map(_HLO)}])
    cfg, mix, model, _ = _load()
    return {"cell": {"name": CELL}, "spans": Spans(), "cfg": cfg, "mix": mix,
            "model": model, "batch": 1, "chips": 1, "traced_steps": 2,
            "trace": {"busy_s": 625e-6},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _read(name, ctx):
    return REG.module("layer_metrics", name + ".py").read(ctx)


def test_readers_on_a_hand_made_trace(reader_ctx):
    cfg, mix, model = (reader_ctx[k] for k in ("cfg", "mix", "model"))
    # the mixers: mix 40 + merge 30 + their grads 70 + 60, over 2 steps
    assert _read("kernel.hyper_connection_ms_per_step", reader_ctx) \
        == pytest.approx(1e3 * 200e-6 / 2)
    _, nbytes = model.hyper_connection_flops_and_bytes_per_sample(cfg, mix)
    assert _read("kernel.hyper_connection_roofline", reader_ctx) \
        == pytest.approx(100.0 * (nbytes / 819e9) / (200e-6 / 2))
    # the attention branch: q_a 10, the kernel 100 + 200, q_a's grad 20 (the
    # gradient of the branch's input); not the FFN's mul, not the block norm
    assert _read("kernel.mla_ms_per_step", reader_ctx) \
        == pytest.approx(1e3 * 330e-6 / 2)
    flops = 3.0 * model.attention_flops_per_sample(cfg, mix)
    assert _read("kernel.mla_attention_roofline", reader_ctx) \
        == pytest.approx(100.0 * (flops / 197e12) / (300e-6 / 2))
    # the expert layer: the router 7, the shared expert's gate 11, a grouped
    # matmul's grad 13, the gradient of the layer's input 9; not the dense
    # FFN's mul
    assert _read("kernel.expert_layer_ms_per_step", reader_ctx) \
        == pytest.approx(1e3 * 40e-6 / 2)


def test_held_rows_reader_takes_the_fullest_expert_of_the_worst_layer():
    cfg, _, _, _ = _load()
    ctx = {"cfg": cfg}
    for layer, counts in ((1, [10] * 8), (2, [10] * 7 + [26])):
        for e, n in enumerate(counts):
            trace.metrics().gauge(
                f"moe.layer_{layer}.moe.tokens_per_expert.{e}").set(n)
    try:
        assert _read("moe.held_rows_max_over_mean", ctx) \
            == pytest.approx(26 * 8 / 96)
        # a configuration that counts its experts under another key
        assert _read("moe.held_rows_max_over_mean", {"cfg": {
            "num_hidden_layers": 5, "num_experts": 8}}) is None
    finally:
        for layer in (1, 2):
            for e in range(8):
                trace.metrics().gauge(
                    f"moe.layer_{layer}.moe.tokens_per_expert.{e}").set(0)
    entry = REG._entry("per_layer", "moe.held_rows_max_over_mean")
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "samples_per_s_per_chip"


@pytest.mark.parametrize("name", [
    "kernel.mla_attention_roofline", "kernel.mla_ms_per_step",
    "kernel.hyper_connection_ms_per_step",
    "kernel.hyper_connection_roofline", "kernel.expert_layer_ms_per_step"])
def test_readers_find_nothing_in_a_program_without_these_ops(name):
    # no trace at all, and a model without the shapes functions: a parent
    # commit's line leaves the metric out and does not raise
    ctx = {"trace": None, "traced_steps": 0, "peaks": None, "cfg": {},
           "mix": {}, "model": object(), "batch": 1, "chips": 1}
    assert _read(name, ctx) is None
    entry = REG._entry("per_layer", name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "mfu"
    assert entry["layer"] == "kernels" and entry["source"] == "device_trace"
