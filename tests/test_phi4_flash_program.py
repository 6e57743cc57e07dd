"""The Phi-4-mini-flash configuration of the benchmark (benchmark/configs/
phi4_mini_flash_train) through Program -> passes -> Executor, at a small
size on the CPU: the loss and every parameter's gradient against its float32
reference, under AMP and without; the uncut layer order with 1 + 7 readers
of the memory and of the shared keys and values; the counts; the tied head;
AMP's colours; the names the per-layer readers look for; and every fault of
the chip check's list, each of which must move a gradient."""
import os

import jax.numpy as jnp
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
CONFIG, CELL = "phi4_mini_flash_train", "phi4_flash_train_seq4096"
# every number shrunk, the graph kept: the six held layers, 4 : 2 heads of 8
# in pairs, a window of 8 over 32 tokens, 64 channels of 4 states
SMALL = {"hidden_size": 32, "intermediate_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "vocab_size": 64, "sliding_window": 8,
         "mamba": {"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 2,
                   "dt_init": [0.001, 0.1]}}
MIX = {"seq_len": 32, "samples_per_chip": 2}
# the chip check's own list (benchmark/tools/check_seeds.py --faults 1)
FAULTS = load_module(os.path.join(REG.config(CONFIG)[1], "faults.py"))


def _load():
    cfg, cfg_dir = REG.config(CONFIG)
    mix = REG.mix(REG.cell(CELL)["traffic"])
    return (cfg, mix, load_module(os.path.join(cfg_dir, "model.py")),
            load_module(os.path.join(cfg_dir, "reference.py")))


def _small(**over):
    cfg, mix, model, reference = _load()
    cfg.update(SMALL)
    cfg.update(over)
    mix.update(MIX)
    return cfg, mix, model, reference


def _batch(cfg, mix, seed=11):
    kind = REG.module("traffic_kinds", mix["kind"] + ".py")
    return kind.generate(mix, cfg, seed, 2, n_batches=1)[0]


def _run_both(amp, seed=11, **over):
    """(the program's loss and every parameter's gradient, the reference's,
    the weights, cfg, the batch)."""
    cfg, mix, model, reference = _small(**over)
    cfg["build_strategy"] = {"amp": amp}
    reset_unique_name()
    built = model.build(cfg, mix, train=False)
    built["startup"].random_seed = seed
    wanted = [p.name for p in built["main"].all_parameters() if p.trainable]
    batch = _batch(cfg, mix, seed)
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(built["startup"])
        scope = fluid.global_scope()
        params = {n: scope.find_var(n) for n in wanted}
        ref_loss, ref_grads = compare.reference_loss_and_grads(
            reference.loss, params, batch, cfg, wanted)
        got = exe.run(fluid.CompiledProgram(
            built["main"], build_strategy=build_strategy(cfg, mix)),
            feed=batch, fetch_list=[built["loss"].name]
            + [built["grads"][w] for w in wanted])
    exe.close()
    mine = (float(np.asarray(got[0]).ravel()[0]),
            dict(zip(wanted, (np.asarray(g) for g in got[1:]))))
    return mine, (float(ref_loss), {w: np.asarray(g)
                                    for w, g in ref_grads.items()}), \
        params, cfg, batch


@pytest.fixture(scope="module")
def float32_run():
    return _run_both(amp=False)


def test_program_equals_reference_in_float32(float32_run):
    (loss, grads), (ref_loss, ref_grads), _, cfg, _ = float32_run
    assert compare.rel_err(loss, ref_loss) < 1e-5
    # 2 Mamba, 2 attention, 1 GMU, 1 cross layer; embedding; final norm
    assert len(grads) == 2 * 9 + 2 * 9 + 2 + 9 + 6 * 6 + 1 + 2
    for name, want in ref_grads.items():
        assert np.abs(want).max() > 0, name
        assert compare.rel_l2(grads[name], want) < 1e-4, name


def test_program_under_amp_is_close_and_not_as_close_as_float32(
        float32_run):
    (_, exact), (_, ref_grads), _, _, _ = float32_run
    (loss, grads), (ref_loss, amp_ref), _, _, _ = _run_both(amp=True)
    assert compare.rel_err(loss, ref_loss) < 2e-3
    worse = 0
    for name, want in amp_ref.items():
        err = compare.rel_l2(grads[name], want)
        # a lambda vector's gradient is one number (d loss / d lam) times a
        # vector, a sum of terms that cancel: bf16 moves it most
        assert err < (0.8 if "lambda" in name else 0.1), (name, err)
        worse += err > 5 * compare.rel_l2(exact[name], ref_grads[name])
    assert worse > 0.8 * len(amp_ref)


@pytest.mark.parametrize("fault", FAULTS.FAULTS)
def test_every_fault_of_the_chip_checks_list_moves_a_gradient(
        fault, float32_run):
    """The reference with one term wrong against the program's float32
    gradients: at least one of the parameters the chip check reads moves by
    far more than float32 rounding (1e-4 above)."""
    (loss, grads), _, params, cfg, batch = float32_run
    wanted = list(REG.config(CONFIG)[0]["check"]["parameters"])
    bad_loss, bad = compare.reference_loss_and_grads(
        FAULTS.planted(fault).loss, params, batch, cfg, wanted)
    errors = {w: compare.rel_l2(grads[w], bad[w]) for w in wanted}
    worst = max(e if np.isfinite(e) else np.inf for e in errors.values())
    assert worst > (1e-3 if fault == "bf16_state" else 5e-2), errors


def test_planting_a_fault_leaves_the_harness_reference_as_it_was():
    """``faults.planted`` bends a copy of the module of its own: the one the
    harness loads for ``correct`` keeps its helpers, and holds no switch."""
    _, _, _, reference = _load()
    before = {n: v for n, v in vars(reference).items() if callable(v)}
    bent = FAULTS.planted("no_subln")
    assert bent is not reference and bent._subln is not reference._subln
    assert {n: v for n, v in vars(reference).items()
            if callable(v)} == before
    assert not hasattr(reference, "FAULTS")


def test_the_uncut_order_has_one_and_seven_readers_and_equals_the_reference():
    """All 32 layers at a small size: 9 Mamba, 9 attention (8 windowed), 7
    gated memory units on layer 16's scan output, 7 cross layers on layer
    17's keys and values; ``append_backward`` sums what the readers send
    back, and every gradient is the reference's."""
    cfg, mix, model, reference = _small(
        num_hidden_layers=32, held_layers=None, hidden_size=16,
        intermediate_size=16, vocab_size=32)
    mix["seq_len"] = 16
    kinds = [model.layer_kind(cfg, i) for i in range(32)]
    assert [k for k, _, _ in kinds].count("ssm") == 9
    assert [(k, bool(w)) for k, w, _ in kinds].count(("attention", True)) == 8
    assert [k for k, _, _ in kinds].count("gmu") == 7
    assert [k for k, _, _ in kinds].count("cross") == 7
    assert [i for i, k in enumerate(kinds) if k[2]] == [16, 17]
    reset_unique_name()
    built = model.build(cfg, mix, train=False)
    block = built["main"].global_block()
    scan16 = next(op for op in block.ops if op.type == "selective_scan"
                  and op.output("Y")[0].startswith("layer_16."))
    memory = scan16.output("Y")[0]
    readers = [op for op in block.ops if op.type == "swiglu"
               and memory in op.input("Y")]
    assert len(readers) == 1 + 7
    attend = [op for op in block.ops
              if op.type == "fused_multihead_attention"]
    assert len(attend) == 16
    keys17 = attend[8].input("K")[0]
    assert keys17.startswith("layer_17.")
    assert sum(op.input("K")[0] == keys17 for op in attend) == 1 + 7
    assert sum(op.input("V")[0] == attend[8].input("V")[0]
               for op in attend) == 1 + 7
    # eight gradients arrive at the memory and are summed into one variable
    sums = [op for op in block.ops if op.type == "sum"
            and op.output("Out")[0].startswith(memory + "@GRAD")]
    assert len(sums) == 1 and len(sums[0].input("X")) == 8

    wanted = ["embed_tokens", "layer_16.ssm.in_proj.w",
              "layer_17.attention.qkv.w", "layer_30.gmu.in_proj.w",
              "layer_31.attention.q.w", "layer_2.ssm.A_log"]
    built["startup"].random_seed = 5
    batch = _batch(cfg, mix, 5)
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(built["startup"])
        scope = fluid.global_scope()
        params = {p.name: scope.find_var(p.name)
                  for p in built["main"].all_parameters()}
        ref_loss, ref_grads = compare.reference_loss_and_grads(
            reference.loss, params, batch, cfg, wanted)
        got = exe.run(built["main"], feed=batch,
                      fetch_list=[built["loss"].name]
                      + [built["grads"][w] for w in wanted])
    exe.close()
    assert compare.rel_err(np.asarray(got[0]).ravel()[0], ref_loss) < 1e-5
    for name, g in zip(wanted, got[1:]):
        assert compare.rel_l2(g, ref_grads[name]) < 2e-4, name


def test_counts_are_the_published_ones():
    cfg, mix, model, _ = _load()
    assert model.param_count(cfg) == 697_094_272
    uncut = dict(cfg, **cfg["published"])
    uncut.pop("held_layers")
    assert model.param_count(uncut) == 3_852_562_944
    assert mix["seq_len"] == 4096
    # 632,750,080 matrix parameters in the six layers and a 25008-row head
    assert abs(model.flops_per_sample(cfg, mix) - 17.99e12) < 0.01e12
    assert model.causal_pairs(4096, 512) == 1_966_336
    assert model.causal_pairs(4096) == 8_390_656
    assert model.attention_flops_per_sample(cfg, mix) \
        == 15_360 * (1_966_336 + 2 * 8_390_656)
    ops, nbytes = model.ssm_scan_flops_and_bytes(cfg, mix)
    assert ops == 2 * 21 * 4096 * 5120 * 16
    assert 0.6e9 < nbytes < 0.8e9
    assert [model.layer_kind(cfg, i)[0] for i in cfg["held_layers"]] \
        == ["ssm", "attention", "ssm", "attention", "gmu", "cross"]
    assert model.layer_kind(cfg, 1)[1] == 512
    assert model.layer_kind(cfg, 17)[1] == 0


def test_param_count_counts_the_programs_parameters():
    cfg, mix, model, _ = _small()
    reset_unique_name()
    built = model.build(cfg, mix, train=True)
    held = sum(int(np.prod(p.shape))
               for p in built["main"].all_parameters() if p.trainable)
    assert held == model.param_count(cfg)


def test_the_tied_heads_gradient_is_the_lookups_plus_the_heads(float32_run):
    (_, grads), (_, ref_grads), params, cfg, batch = float32_run
    _, lookup_only = compare.reference_loss_and_grads(
        FAULTS.planted("untied_head").loss, params, batch, cfg,
        ["embed_tokens"])
    lookup = np.asarray(lookup_only["embed_tokens"])
    head = ref_grads["embed_tokens"] - lookup
    assert np.linalg.norm(head) > 0.1 * np.linalg.norm(lookup)
    assert compare.rel_l2(grads["embed_tokens"], lookup + head) < 1e-4
    # in the Program: one `sum` of the two ops' gradients
    cfg, mix, model, _ = _small()
    reset_unique_name()
    block = model.build(cfg, mix, train=False)["main"].global_block()
    total = next(op for op in block.ops if op.type == "sum"
                 and op.output("Out")[0].startswith("embed_tokens@GRAD"))
    assert len(total.input("X")) == 2
    head_op = next(op for op in block.ops
                   if op.type == "linear_cross_entropy")
    assert head_op.input("W") == ["embed_tokens"]
    assert head_op.attr("transpose_w") is True


def test_a_tied_head_refuses_a_matrix_of_another_shape():
    from paddle_tpu.fluid import layers as L
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", [-1, 4], dtype="int64")
        x = L.embedding(ids, [10, 8])
        table = main.all_parameters()[0]
        with pytest.raises(ValueError, match="tied_to"):
            L.linear_cross_entropy(x, L.unsqueeze(ids, [2]), 12,
                                   tied_to=table)


def test_amp_keeps_the_scan_and_the_stream_float32():
    """In the rewritten program the scan's operands, the sub-norm and the
    residual stream are float32; the matmuls, the convolution and the
    attention kernel take bfloat16."""
    cfg, mix, model, _ = _small()
    cfg["build_strategy"] = {"amp": True}
    reset_unique_name()
    built = model.build(cfg, mix, train=False)
    program = fluid.CompiledProgram(built["main"],
                                    build_strategy=build_strategy(cfg, mix))
    program._apply_ir_passes([built["loss"].name])
    block = built["main"].global_block()

    def dtypes(op_type, slot):
        out = set()
        for op in block.ops:
            if op.type == op_type:
                # a folded cast: {slot: [dtype or None, ...]}
                cast = (op.attrs.get("__amp_cast__") or {}).get(slot) or []
                for j, name in enumerate(op.input(slot)):
                    folded = cast[j] if j < len(cast) else None
                    out.add(str(folded or block.var(name).dtype))
        return out
    for slot in ("X", "Dt", "A", "B", "C", "D"):
        assert dtypes("selective_scan", slot) == {"float32"}, slot
    assert dtypes("rms_norm", "X") == {"float32"}
    assert dtypes("causal_conv1d", "X") == {"bfloat16"}
    assert dtypes("fused_multihead_attention", "Q") == {"bfloat16"}
    assert dtypes("layer_norm", "X") == {"float32"}


def test_every_mixer_and_mlp_output_carries_its_layers_name():
    cfg, mix, model, _ = _small()
    reset_unique_name()
    block = model.build(cfg, mix, train=False)["main"].global_block()
    forward = [op for op in block.ops if not op.attrs.get("op_role", 0)]
    kinds = {0: "ssm", 1: "attention", 16: "ssm", 17: "attention",
             18: "gmu", 19: "attention"}
    for i, kind in kinds.items():
        named = [op for op in forward if any(
            n.startswith(f"layer_{i}.{kind}.") for n in op.output_arg_names)]
        assert len(named) >= 4, (i, kind)
        assert any(n.startswith(f"layer_{i}.mlp.") for op in forward
                   for n in op.output_arg_names)
    scans = [op.output("Y")[0] for op in forward
             if op.type == "selective_scan"]
    assert [s.split(".scan")[0] for s in scans] == ["layer_0.ssm",
                                                    "layer_16.ssm"]
    # nothing but the residual adds, casts and the loss is unnamed
    unnamed = {op.type for op in forward
               if not any(n.startswith(("layer_", "final_norm", "lm_head"))
                          for n in op.output_arg_names)}
    assert unnamed <= {"lookup_table_v2", "cast", "elementwise_add",
                       "unsqueeze2", "mean", "exp", "assign"}, unnamed


def test_the_lambdas_are_published_when_a_runner_drains():
    from paddle_tpu.fluid.async_pipeline import AsyncStepRunner
    cfg, mix, model, _ = _small()
    reset_unique_name()
    built = model.build(cfg, mix, train=True)
    built["startup"].random_seed = 3
    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(built["startup"])
        runner = AsyncStepRunner(exe, built["main"], [built["loss"]])
        runner.submit(_batch(cfg, mix, 3))
        runner.drain()
    exe.close()
    for i in (1, 17, 19):
        assert abs(trace.gauge_value(f"diff_attention.layer_{i}.lambda", 9.0)
                   - model.lambda_init(i)) < 0.3
    assert trace.gauge_value("ssm.layer_16.state_abs_max", -1.0) > 0
    assert 1e-3 < trace.gauge_value("ssm.layer_0.dt_mean", -1.0) < 0.15
