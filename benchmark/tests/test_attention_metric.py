"""``kernel.attention_ms_per_step``: the rows of the table by Program op that
the fused attention op and its grad own, and nothing where there is no such
row or no table."""
from benchmark.harness import program_ops
from benchmark.harness.registry import Registry

NAME = "kernel.attention_ms_per_step"


def _read(ctx):
    return Registry().module("layer_metrics", NAME + ".py").read(ctx)


def _ctx(labels):
    table = None if labels is None else {
        "labels": [{"label": l, "seconds": s} for l, s in labels]}
    return {"program_ops": table, "traced_steps": 4}


def test_sums_the_forward_and_grad_rows_per_step():
    ctx = _ctx([("mul", 0.5), ("fused_multihead_attention", 0.012),
                ("fused_multihead_attention_grad", 0.028), ("softmax", 0.1)])
    assert abs(_read(ctx) - 10.0) < 1e-9


def test_nothing_without_the_op_or_without_a_table():
    assert _read(_ctx([("mul", 0.5), ("softmax", 0.1)])) is None
    assert _read(_ctx(None)) is None


def test_benchmark_json_lists_it_for_the_cells_where_the_kernel_engages():
    reg = Registry()
    m = {m["name"]: m for m in reg.spec["per_layer"]}[NAME]
    assert reg.spec["per_layer"][-1] is m
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == ("ms", "lower", "device_trace", "kernels", "mfu")
    assert "bert_base_seq512" in m["workloads"]
    assert set(m["workloads"]) <= {"bert_base_seq128", "bert_base_seq512"}
    assert program_ops._KEY == "program_ops"
