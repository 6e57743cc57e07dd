"""The Mellum2 configuration of the benchmark (benchmark/configs/
mellum2_12b_a2_5b_train) through Program -> passes -> Executor, at a small
size on the CPU: against its float32 reference, under AMP, its counts, the
device-side routing counters, and the other configurations' Programs left as they were."""
import hashlib
import os

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import trace
from paddle_tpu.fluid.core import Scope, scope_guard
from paddle_tpu.fluid.framework import reset_unique_name

from benchmark.harness import compare
from benchmark.harness.registry import Registry, load_module
from benchmark.harness.strategy import build_strategy

REG = Registry()
# every number shrunk, the graph kept: one period of layers, 8:2 grouped
# heads, a window shorter than the sequence, 4 of 16 experts held, top-4
SMALL = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 8,
         "num_key_value_heads": 2, "vocab_size": 256,
         "moe_intermediate_size": 32, "num_experts": 4,
         "num_experts_per_tok": 4, "sliding_window": 8, "first_expert": 4,
         "published": {"num_hidden_layers": 28, "num_experts": 16,
                       "vocab_size": 1024}}
MIX = {"seq_len": 32, "samples_per_chip": 2}
WANTED = ["embed_tokens", "layer_0.attention.query.w",
          "layer_3.attention.key.w", "layer_1.router.w",
          "layer_2.experts.down"]


def _load(config="mellum2_12b_a2_5b_train", cell="mellum2_train_seq8192"):
    cfg, cfg_dir = REG.config(config)
    mix = REG.mix(REG.cell(cell)["traffic"])
    return (cfg, mix, load_module(os.path.join(cfg_dir, "model.py")),
            load_module(os.path.join(cfg_dir, "reference.py")))


def _small():
    cfg, mix, model, reference = _load()
    cfg.update(SMALL)
    mix.update(MIX)
    cfg["check"] = {"samples": 2, "parameters": WANTED,
                    "loss_rel_tol": 1.0, "grad_rel_l2_tol": 1.0}
    return cfg, mix, model, reference


def _against_reference(amp, seed=11):
    cfg, mix, model, reference = _small()
    cfg["build_strategy"] = {"amp": amp}
    kind = REG.module("traffic_kinds", mix["kind"] + ".py")
    batch = kind.generate(mix, cfg, seed, 2, n_batches=1)[0]
    reset_unique_name()
    train = model.build(cfg, mix, train=True)
    train["startup"].random_seed = seed
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


def test_program_equals_reference_in_float32():
    report = _against_reference(amp=False)
    assert report["loss_rel_err"] < 1e-5
    assert max(report["grad_rel_l2"].values()) < 1e-4, report


def test_program_under_amp_is_close_and_not_as_close_as_float32():
    exact = _against_reference(amp=False)
    amp = _against_reference(amp=True)
    assert amp["loss_rel_err"] < 2e-3, amp
    # bf16 operands: percents on the dense gradients; the router's also
    # carries the tokens whose last chosen expert differs from the
    # reference's (64 tokens here, so one swap is a large share)
    for name, err in amp["grad_rel_l2"].items():
        assert err < (0.6 if "router" in name else 0.08), amp
        assert err > 10 * exact["grad_rel_l2"][name], (name, amp, exact)


def test_amp_keeps_router_norms_and_loss_in_float32():
    cfg, mix, model, _ = _small()
    cfg["build_strategy"] = {"amp": True}
    reset_unique_name()
    built = model.build(cfg, mix, train=True)
    prog = fluid.CompiledProgram(built["main"],
                                 build_strategy=build_strategy(cfg, mix))
    prog._apply_ir_passes([built["loss"].name])
    block = built["main"].global_block()
    casts = {}
    for op in block.ops:
        if op.attrs.get("op_role", 0):
            continue
        casts.setdefault(op.type, []).append(
            op.attrs.get("__amp_cast__") or {})
    assert all(set(c.get("X", [])) <= {"float32"} for c in casts["rms_norm"])
    assert all("RouterWeight" not in c and set(c.get("X", [None]))
               <= {None, "float32"} for c in casts["moe_route"])
    assert all(c.get("W") == ["bfloat16"]
               for c in casts["moe_grouped_matmul"])
    assert all("TopKWeight" not in c for c in casts["moe_combine"])
    assert all(set(c.get("Logits", [])) <= {"float32"}
               for c in casts["softmax_with_cross_entropy"])
    types = [op.type for op in block.ops if not op.attrs.get("op_role", 0)]
    assert types.count("fused_multihead_attention") == 4
    assert types.count("moe_grouped_matmul") == 12
    assert types.count("moe_route") == types.count("moe_combine") == 4
    windows = [op.attrs["window"] for op in block.ops
               if op.type == "fused_multihead_attention"]
    assert windows == [8, 8, 8, 0]
    assert all(op.attrs["causal"] and op.attrs["num_kv_heads"] == 2
               for op in block.ops if op.type == "fused_multihead_attention")


def test_param_count():
    cfg, mix, model, _ = _load()
    assert model.param_count(cfg) == 595_153_152
    uncut = dict(cfg, **cfg["published"])
    assert model.param_count(uncut) == 12_149_915_904
    small, small_mix, _, _ = _small()
    reset_unique_name()
    built = model.build(small, small_mix, train=True)
    counted = sum(int(np.prod(p.shape))
                  for p in built["main"].all_parameters() if p.trainable)
    assert counted == model.param_count(small)
    # the issue's reckoning of a step's required work
    assert abs(model.flops_per_sample(cfg, mix) / 3 / mix["seq_len"]
               - 497.7e6) < 0.2e6
    assert model.attended_pairs(8192, 1024) == 7_864_832
    assert model.attended_pairs(8192, 0) == 8192 * 8193 // 2
    assert model.attended_pairs(512, 1024) == 512 * 513 // 2


def test_config_states_its_cut():
    cfg, mix, _, _ = _load()
    assert cfg["source"].startswith("https://huggingface.co/JetBrains/")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                "vocab_size": 98304}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 24576)
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert "four chips" in cfg["deployment"]
    for width, value in {"hidden_size": 2304, "num_attention_heads": 32,
                         "num_key_value_heads": 4, "head_dim": 128,
                         "sliding_window": 1024, "num_experts_per_tok": 8,
                         "moe_intermediate_size": 896,
                         "rms_norm_eps": 1e-6}.items():
        assert cfg[width] == value
    assert set(cfg["check"]["parameters"]) >= set(WANTED)


def test_routing_counts_leave_the_device_when_the_runner_drains():
    from paddle_tpu.fluid.async_pipeline import AsyncStepRunner
    cfg, mix, model, _ = _small()
    kind = REG.module("traffic_kinds", mix["kind"] + ".py")
    feeds = kind.generate(mix, cfg, 5, 2, n_batches=3)
    reset_unique_name()
    built = model.build(cfg, mix, train=True)
    assert built["main"]._hints["device_counters"][
        "layer_2.moe.tokens_per_expert"] == "moe.layer_2.moe.tokens_per_expert"
    exe = fluid.Executor()

    def gauge(name):
        return trace.gauge_value("moe.layer_2.moe." + name, -1.0)
    with scope_guard(Scope()):
        exe.run(built["startup"])
        runner = AsyncStepRunner(exe, built["main"], [built["loss"]])
        for feed in feeds:
            runner.submit(feed)
        runner.drain()
        assert gauge("steps") == 3
        counts = [gauge(f"tokens_per_expert.{i}") for i in range(4)]
        held = np.asarray(fluid.global_scope().find_var(
            "layer_2.moe.tokens_per_expert"))
        np.testing.assert_array_equal(counts, held)
        # 3 steps x 64 tokens x top-4 of 16 experts, a quarter of them held
        assert 0 < sum(counts) <= 3 * 64 * 4
        # the share of the buffers' 64 x 4 rows the permutation visits
        assert gauge("rows_visited_share") == sum(counts) / (3 * 64 * 4)
        runner.submit(feeds[0])
        runner.drain()
        assert gauge("steps") == 4
    exe.close()


# the op stream (type, inputs, outputs, attribute names) the default
# pipeline leaves of the other configurations' Programs, at a small size at
# which the attention pass still fires, as the tree before this configuration
# left it (PR 25; dp4 as PR 27 left it)
PARENT_STREAMS = {
    "bert_base_seq128": (230, 2, "a13010b473eb204e92d84e73adad87a97af0f4e14"
                                 "227c5ee00009dc63b188880"),
    "bert_base_seq512": (230, 2, "a13010b473eb204e92d84e73adad87a97af0f4e14"
                                 "227c5ee00009dc63b188880"),
    # since PR 27 the data-parallel program gets the attention pass too (the
    # kernel runs once per chip): its stream is seq128's, op for op (it was
    # the unfused 250 ops, 84f464f8..87510)
    "bert_base_seq128_dp4": (230, 2, "a13010b473eb204e92d84e73adad87a97af0f4"
                                     "e14227c5ee00009dc63b188880"),
    "resnet50_b256": (328, 0, "f9cf2c507f06e0e6f0092f8eff59803575bbdaebaa1c1"
                              "dec518db55dab073595"),
}
SMALL_OTHERS = {
    "bert_base_pretrain": (
        {"vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 2, "intermediate_size": 256,
         "max_position_embeddings": 512}, {"samples_per_chip": 2}),
    "resnet50": (
        {"image_size": 64, "stem_width": 8, "stage_blocks": [1, 2, 1, 1],
         "stage_widths": [8, 16, 32, 64], "num_classes": 10},
        {"image_size": 64, "samples_per_chip": 2}),
}


@pytest.mark.parametrize("cell", sorted(PARENT_STREAMS))
def test_other_programs_come_out_of_the_pipeline_as_before(cell):
    entry = REG.cell(cell)
    cfg, cfg_dir = REG.config(entry["config"])
    mix = REG.mix(entry["traffic"])
    cfg.update(SMALL_OTHERS[entry["config"]][0])
    mix.update(SMALL_OTHERS[entry["config"]][1])
    reset_unique_name()
    model = load_module(os.path.join(cfg_dir, "model.py"))
    built = model.build(cfg, mix, train=True)
    program = fluid.CompiledProgram(built["main"],
                                    build_strategy=build_strategy(cfg, mix))
    if mix.get("layout", {}).get("sharding"):
        program._ensure_sharding_plan()
    program._apply_ir_passes([built["loss"].name])
    ops = built["main"].global_block().ops
    lines = [f"{op.type}"
             f"|{sorted((k, tuple(v)) for k, v in op.inputs.items())}"
             f"|{sorted((k, tuple(v)) for k, v in op.outputs.items())}"
             f"|{sorted(k for k in op.attrs)}" for op in ops]
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()
    fused = [op.type for op in ops].count("fused_multihead_attention")
    assert (len(ops), fused, digest) == PARENT_STREAMS[cell]
