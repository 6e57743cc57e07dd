"""The sparse-expert layer (parallel/moe.py, the ``moe_*`` ops): top-k
routing without dropping, a chip that holds a range of the experts, the
shares adding up to the whole layer, and expert parallelism over ``ep``."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu  # noqa: F401 — registers the lowerings
from paddle_tpu.parallel import mesh as pmesh, moe
from paddle_tpu.parallel.api import compat_shard_map as shard_map

T, D, E, F, K = 64, 16, 8, 12, 3


def _weights(seed=0, t=T, d=D, e=E, f=F):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (t, d)), jax.random.normal(k[1], (d, e)),
            jax.random.normal(k[2], (e, d, f)) * 0.3,
            jax.random.normal(k[3], (e, d, f)) * 0.3,
            jax.random.normal(k[4], (e, f, d)) * 0.3)


def _dense(x, router, gate, up, down, top_k=K, held=None):
    """Every expert over every token, masked: the plain spelling."""
    top, chosen = jax.lax.top_k(x @ router, top_k)
    weights = jax.nn.softmax(top, -1)
    out = jnp.zeros_like(x)
    for e in (range(gate.shape[0]) if held is None else held):
        w = jnp.sum(jnp.where(chosen == e, weights, 0), -1, keepdims=True)
        out = out + w * ((jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    return out


def _reference_module():
    from benchmark.harness.registry import Registry, load_module
    _, cfg_dir = Registry().config("mellum2_12b_a2_5b_train")
    return load_module(os.path.join(cfg_dir, "reference.py"))


def test_shapes_and_routing_on_one_device():
    x, router, gate, up, down = _weights()
    weights, experts = moe.route(x, router, K)
    assert weights.shape == (T, K) and experts.shape == (T, K)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
    assert experts.dtype == jnp.int32
    # the K chosen are distinct and are the K largest logits
    assert all(len(set(row)) == K for row in np.asarray(experts))
    plan = moe.dispatch_plan(experts, 0, E)
    assert int(plan.group_sizes.sum()) == T * K
    counts = np.bincount(np.asarray(experts).ravel(), minlength=E)
    np.testing.assert_array_equal(np.asarray(plan.group_sizes), counts)
    # rows are sorted by expert, and pos is the inverse of order
    by_row = np.asarray(experts).ravel()[np.asarray(plan.order)]
    assert (np.diff(by_row) >= 0).all()
    np.testing.assert_array_equal(
        np.asarray(plan.order)[np.asarray(plan.pos).ravel()],
        np.arange(T * K))
    out = moe.expert_layer(x, router, gate, up, down, top_k=K)
    assert out.shape == (T, D)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense(x, router, gate, up, down)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("scale", [1.0, 2.0])
def test_sigmoid_scoring_chooses_by_the_biased_score_and_weighs_without_it(
        scale):
    x, router, _, _, _ = _weights()
    bias = jnp.asarray(np.random.RandomState(1).randn(E) * 0.5, jnp.float32)
    weights, experts = moe.route(x, router, K, "sigmoid", bias, scale)
    scores = np.asarray(jax.nn.sigmoid(x @ router), np.float64)
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :K]
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(want, -1))
    chosen = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(
        np.asarray(weights), scale * chosen / chosen.sum(-1, keepdims=True),
        rtol=2e-5)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), scale, rtol=1e-5)
    # the bias moves the choice: without it other experts are kept
    _, unbiased = moe.route(x, router, K, "sigmoid", None, scale)
    assert (np.sort(np.asarray(unbiased), -1)
            != np.sort(np.asarray(experts), -1)).any()


def test_softmax_scoring_is_the_default_and_unknown_scorings_are_refused():
    x, router, _, _, _ = _weights()
    weights, experts = moe.route(x, router, K)
    named, same = moe.route(x, router, K, "softmax", None, 1.0)
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(named))
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(same))
    top = np.sort(np.asarray(x @ router), -1)[:, ::-1][:, :K]
    e = np.exp(top - top.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(weights),
                               e / e.sum(-1, keepdims=True), rtol=2e-5)
    doubled, _ = moe.route(x, router, K, "softmax", None, 2.0)
    np.testing.assert_allclose(np.asarray(doubled), 2 * np.asarray(weights),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(x, router, K, "tanh")


def test_sigmoid_layer_equals_the_dense_spelling():
    x, router, gate, up, down = _weights()
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(9), (E,))
    out = moe.expert_layer(x, router, gate, up, down, top_k=K,
                           scoring="sigmoid", bias=bias, scale=2.0)
    scores = jax.nn.sigmoid(x @ router)
    _, chosen = jax.lax.top_k(scores + bias, K)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = 2.0 * picked / picked.sum(-1, keepdims=True)
    want = jnp.zeros_like(x)
    for e in range(E):
        w = jnp.sum(jnp.where(chosen == e, weights, 0), -1, keepdims=True)
        want = want + w * ((jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_gradients_equal_the_dense_spelling():
    args = _weights(1)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(
        moe.expert_layer(*a, top_k=K))), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(_dense(*a))),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, r in zip(("x", "router", "gate", "up", "down"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-4,
                                   atol=1e-4, err_msg=name)


def test_no_drop_at_overflow():
    """Every token's top choice is expert 0: a capacity router would keep
    T / E of them.  Here all T rows reach it and each gets its result."""
    x, router, gate, up, down = _weights(2)
    x = jnp.abs(x)
    router = jnp.zeros((D, E)).at[:, 0].set(10.0) + 0.01 * router
    _, experts = moe.route(x, router, K)
    assert (np.asarray(experts)[:, 0] == 0).all()
    plan = moe.dispatch_plan(experts, 0, E)
    assert int(plan.group_sizes[0]) == T
    out = moe.expert_layer(x, router, gate, up, down, top_k=K)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense(x, router, gate, up, down)),
                               rtol=2e-5, atol=2e-5)
    assert (np.abs(np.asarray(out)).sum(-1) > 0).all()


def _argsort_plan(experts, first, held, max_rows):
    """The oracle: the plan as a stable argsort of the key and its inverse,
    on the host."""
    local = np.asarray(experts).reshape(-1) - first
    key = np.where((local >= 0) & (local < held), local, held)
    order = np.argsort(key, kind="stable").astype(np.int32)
    pos = np.argsort(order).astype(np.int32).reshape(np.shape(experts))
    return (order[:max_rows], pos,
            np.bincount(key, minlength=held + 1)[:held].astype(np.int32))


def _plan_case(case):
    """(experts [T, top_k], first_expert, num_held, max_rows) of a case."""
    rng = np.random.RandomState(3)

    def routed(tokens, top_k, experts):
        return np.stack([rng.permutation(experts)[:top_k]
                         for _ in range(tokens)]).astype(np.int32)
    if case == "none_held":                # a range nobody is routed to
        return routed(64, 4, 16), 20, 2, None
    if case == "one_row":
        chosen = rng.randint(1, 16, (64, 4)).astype(np.int32)
        chosen[37, 2] = 0
        return chosen, 0, 1, None
    if case == "all_held":
        return routed(64, 4, 16), 0, 16, None
    if case == "ragged":
        return routed(96, 3, 16), 0, 5, None
    if case == "first_expert_inside":
        return routed(64, 4, 16), 6, 4, None
    if case == "bounded":                  # twice the even share
        return routed(512, 4, 16), 0, 2, 2 * 512 * 4 * 2 // 16
    if case == "keys_not_whole_tiles":     # R = 150
        return routed(50, 3, 16), 2, 5, 100
    if case == "top22_of_512":             # nemotron's routing, 64 tokens
        return routed(64, 22, 512), 0, 8, 64
    if case == "ep_by_chip":               # expert_layer's plan by chip
        return routed(64, 4, 16) // 4, 0, 4, None
    if case == "ep_received":              # ... and the received rows'
        got = rng.randint(0, 5, (4 * 64, 1)).astype(np.int32)
        return got, 0, 4, None
    raise ValueError(case)


_PLAN_CASES = ["none_held", "one_row", "all_held", "ragged",
               "first_expert_inside", "bounded", "keys_not_whole_tiles",
               "top22_of_512", "ep_by_chip", "ep_received"]


@pytest.mark.parametrize("lowering", [None, "counting", "sort"])
@pytest.mark.parametrize("case", _PLAN_CASES)
def test_the_counted_plan_equals_two_argsorts(case, lowering, monkeypatch):
    """``dispatch_plan`` against the argsort oracle, bit for bit, in Order
    (rows past ``n`` and the ``max_rows`` cut included), Pos and
    GroupSizes: as the shapes choose (None) and under each construction."""
    experts, first, held, max_rows = _plan_case(case)
    if lowering is not None:
        monkeypatch.setattr(moe, "plan_lowering", lambda *a: lowering)
    plan = jax.jit(lambda e: moe.dispatch_plan(e, first, held, max_rows))(
        jnp.asarray(experts))
    want = _argsort_plan(experts, first, held, max_rows)
    for name, got, w in zip(("order", "pos", "group_sizes"), plan, want):
        assert got.dtype == jnp.int32, name
        np.testing.assert_array_equal(np.asarray(got), w, err_msg=name)


def test_the_cells_plans_are_counted_without_a_sort():
    """What each expert cell's shape picks, the counter, and no sort in
    the plan at nemotron3_super_train_seq4096's shape (4096 tokens, top-22,
    8 held, 3072 rows)."""
    from paddle_tpu.fluid import trace
    assert moe.plan_lowering(9, 3072, 4096 * 22) == "counting"
    # mellum2, xing4 and keye_vl2: Order's rows are too many for the search
    for groups, rows, keys in ((17, 65536, 65536), (9, 16384, 16384),
                               (17, 32768, 131072)):
        assert moe.plan_lowering(groups, rows, keys) == "sort"
    assert moe.plan_lowering(65, 64, 4096) == "sort"
    counter = trace.metrics().counter("moe.plan_lowering.counting")
    before = counter.value
    jaxpr = jax.make_jaxpr(lambda e: moe.dispatch_plan(e, 0, 8, 3072))(
        jax.ShapeDtypeStruct((4096, 22), jnp.int32))
    assert counter.value == before + 1
    assert "sort" not in {e.primitive.name for e in jaxpr.jaxpr.eqns}


@pytest.mark.parametrize("case", ["all_to_one_held", "none_held"])
def test_dropless_under_skew_on_a_share(case):
    """A chip that holds experts 2..3 of 8: every token routed to held
    expert 2 (among its K), or no token routed to a held expert at all."""
    x, router, gate, up, down = _weights(3)
    x = jnp.abs(x)
    push = {"all_to_one_held": [2, 5, 6], "none_held": [0, 5, 6]}[case]
    router = 0.01 * router
    for e in push:
        router = router.at[:, e].add(10.0)
    first, held = 2, 2
    _, experts = moe.route(x, router, K)
    plan = moe.dispatch_plan(experts, first, held)
    want_rows = T if case == "all_to_one_held" else 0
    assert int(plan.group_sizes.sum()) == want_rows
    out = moe.expert_layer(x, router, gate[first:first + held],
                           up[first:first + held], down[first:first + held],
                           top_k=K, first_expert=first)
    want = _dense(x, router, gate, up, down,
                  held=range(first, first + held))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    if case == "none_held":
        assert not np.asarray(out).any()
        grads = jax.grad(lambda g: jnp.sum(moe.expert_layer(
            x, router, g, up[first:first + held], down[first:first + held],
            top_k=K, first_expert=first)))(gate[first:first + held])
        assert np.isfinite(np.asarray(grads)).all() \
            and not np.asarray(grads).any()


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The share tied to the model: the configuration's reference given all
    experts is the whole layer; four chips holding a quarter each, through
    the program's layer, add up to it."""
    ref = _reference_module()
    x, router, gate, up, down = _weights(4)
    whole = ref._held_experts(x, router, gate, up, down, K, 0)
    held = E // 4
    shares = [moe.expert_layer(x, router, gate[i:i + held], up[i:i + held],
                               down[i:i + held], top_k=K, first_expert=i)
              for i in range(0, E, held)]
    np.testing.assert_allclose(np.asarray(sum(shares)), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    # and each share is what the reference computes when given that share
    for i, share in zip(range(0, E, held), shares):
        want = ref._held_experts(x, router, gate[i:i + held],
                                 up[i:i + held], down[i:i + held], K, i)
        np.testing.assert_allclose(np.asarray(share), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(shares[0] - whole)).max() > 1e-2


@pytest.mark.parametrize("skewed", [False, True])
def test_expert_parallel_over_ep_equals_the_single_device_layer(skewed):
    """Tokens and experts sharded over four devices, the two all-to-alls in
    between: the exchange the one-chip cell leaves out.  Also when every
    token goes to the experts of one device."""
    mesh = pmesh.build_mesh({"ep": 4})
    try:
        x, router, gate, up, down = _weights(5, t=32)
        if skewed:
            x = jnp.abs(x)
            router = 0.01 * router
            for e in (0, 1, 4):
                router = router.at[:, e].add(10.0)
        sharded = P("ep", None, None)
        layer = jax.jit(shard_map(
            lambda *a: moe.expert_layer(*a, top_k=K, axis_name="ep"),
            mesh=mesh, in_specs=(P("ep", None), P(), sharded, sharded,
                                 sharded), out_specs=P("ep", None)))
        want = moe.expert_layer(x, router, gate, up, down, top_k=K)
        np.testing.assert_allclose(
            np.asarray(layer(x, router, gate, up, down)), np.asarray(want),
            rtol=2e-5, atol=2e-5)
        got = jax.grad(lambda *a: jnp.sum(jnp.sin(layer(*a))),
                       argnums=(0, 1, 2))(x, router, gate, up, down)
        ref = jax.grad(lambda *a: jnp.sum(jnp.sin(
            moe.expert_layer(*a, top_k=K))), argnums=(0, 1, 2))(
                x, router, gate, up, down)
        for a, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-4)
    finally:
        pmesh.set_current_mesh(None)


def test_partition_rules():
    from paddle_tpu.parallel import sharding as shd
    specs = shd.match_partition_rules(
        moe.moe_partition_rules(),
        {"layer_3.router.w": (16, 8), "layer_3.experts.gate": (8, 16, 32),
         "layer_3.experts.up": (8, 16, 32),
         "layer_3.experts.down": (8, 32, 16)}, on_unmatched="raise")
    assert specs["layer_3.router.w"] == P()
    for name in ("gate", "up", "down"):
        assert specs[f"layer_3.experts.{name}"] == P("ep", None, None)


def test_grouped_matmul_rows_past_the_last_group_are_zero():
    x = jnp.ones((16, 4))
    w = jnp.stack([jnp.eye(4) * (i + 1) for i in range(3)])
    sizes = jnp.asarray([3, 0, 5], jnp.int32)
    out = np.asarray(moe.grouped_matmul(x, w, sizes))
    assert (out[:3] == 1).all() and (out[3:8] == 3).all()
    assert not out[8:].any()
    assert moe.gmm_lowering(True, jnp.ones((512, 128)),
                            jnp.ones((2, 128, 256))) == "megablox"
    assert moe.gmm_lowering(True, x, w) == "ragged_dot"
    assert moe.gmm_lowering(False, jnp.ones((512, 128)),
                            jnp.ones((2, 128, 256))) == "ragged_dot"


def test_megablox_lowering_in_interpret_mode():
    """The kernel path of grouped_matmul, forward and gradient, against
    ragged_dot, with rows past the last group."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 128, 128)) * 0.1
    sizes = jnp.asarray([100, 0, 413], jnp.int32)

    def run(use_kernel):
        f = lambda x, w: moe.grouped_matmul(x, w, sizes, use_kernel)
        out, vjp = jax.vjp(f, x, w)
        return (out,) + vjp(jnp.ones_like(out))
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        got = run(True)
    want = run(False)
    for a, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-4,
                                   atol=1e-4)
    assert not np.asarray(got[0])[513:].any()
    assert not np.asarray(got[1])[513:].any()


# ---------------------------------------------------------------------------
# the permutation visits only the rows a chip holds.  The plain reference is
# the four formulas the module had until PR 35: ``take`` through ``order`` /
# ``pos`` over every row of the buffer and every (t, k), masked afterwards.
# ---------------------------------------------------------------------------

def _ref_masks(plan):
    n = jnp.sum(plan.group_sizes)
    return (jnp.arange(plan.order.shape[0]) < n)[:, None], plan.pos < n


def _ref_dispatch(x, plan):
    valid, _ = _ref_masks(plan)
    return jnp.where(valid, jnp.take(x, plan.order // plan.pos.shape[1],
                                     axis=0), 0)


def _ref_dispatch_bwd(plan, g):
    _, held = _ref_masks(plan)
    rows = jnp.take(g, plan.pos, axis=0, mode="clip")           # [T, K, D]
    return jnp.sum(jnp.where(held[..., None], rows, 0).astype(jnp.float32),
                   axis=1).astype(g.dtype)


def _ref_combine(y, weights, plan):
    _, held = _ref_masks(plan)
    rows = jnp.take(y, plan.pos, axis=0, mode="clip")           # [T, K, D]
    return jnp.sum(jnp.where(held[..., None], rows.astype(jnp.float32)
                             * weights[..., None].astype(jnp.float32), 0),
                   axis=1).astype(y.dtype)


def _ref_combine_bwd(y, weights, plan, g):
    valid, held = _ref_masks(plan)
    top_k = plan.pos.shape[1]
    rows = jnp.take(y, plan.pos, axis=0, mode="clip")           # [T, K, D]
    gw = jnp.sum(rows.astype(jnp.float32)
                 * g[:, None, :].astype(jnp.float32), axis=-1)
    gw = jnp.where(held, gw, 0).astype(weights.dtype)
    w_row = jnp.take(weights.reshape(-1), plan.order)
    g_row = jnp.take(g, plan.order // top_k, axis=0)
    gy = jnp.where(valid, g_row.astype(jnp.float32)
                   * w_row[:, None].astype(jnp.float32), 0)
    return gy.astype(y.dtype), gw


def _held_plan(case, seed=0):
    """(tokens, top_k, width, plan) of a routing whose held share ``n / R``
    is the case's.  512 rows are one block of the gathers."""
    tokens, top_k, width, experts = 512, 4, 128, 16
    max_rows = None
    rng = np.random.RandomState(seed)
    chosen = np.stack([rng.permutation(experts)[:top_k]
                       for _ in range(tokens)]).astype(np.int32)
    first, held = 0, {"none": 0, "eighth": 2, "quarter": 4, "all": 16,
                      "ragged": 5, "bounded": 2}.get(case, 0)
    if case == "none":
        first, held = 20, 2                    # a range nobody is routed to
    elif case == "one_row":
        chosen[:] = rng.randint(1, experts, chosen.shape)
        chosen[37, 2] = 0
        held = 1
    elif case == "bounded":                    # keye's: twice the even share
        max_rows = 2 * tokens * top_k * held // experts
    plan = moe.dispatch_plan(jnp.asarray(chosen), first, held, max_rows)
    return tokens, top_k, width, plan


@pytest.mark.parametrize("case", ["none", "one_row", "eighth", "quarter",
                                  "all", "ragged", "bounded"])
def test_the_permutation_equals_the_plain_formulas_and_reads_held_rows_only(
        case):
    tokens, top_k, width, plan = _held_plan(case)
    total, n = plan.order.shape[0], int(jnp.sum(plan.group_sizes))
    want_n = {"none": 0, "one_row": 1, "all": tokens * top_k}.get(case)
    assert want_n is None or n == want_n
    assert case != "ragged" or n % 512
    assert case != "bounded" or (total == tokens * top_k // 4 and n < total)
    k = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(k[0], (tokens, width))
    y = jax.random.normal(k[1], (total, width))
    weights = jax.nn.softmax(jax.random.normal(k[2], (tokens, top_k)), -1)
    g_rows = jax.random.normal(k[3], (total, width))
    g_tokens = jax.random.normal(k[4], (tokens, width))

    rows, vjp = jax.vjp(lambda x: moe.dispatch(x, plan), x)
    np.testing.assert_array_equal(np.asarray(rows),
                                  np.asarray(_ref_dispatch(x, plan)))
    assert not np.asarray(rows)[n:].any()
    np.testing.assert_allclose(
        np.asarray(vjp(g_rows)[0]), np.asarray(_ref_dispatch_bwd(plan, g_rows)),
        rtol=1e-6, atol=1e-6)

    out, vjp = jax.vjp(lambda y, w: moe.combine(y, w, plan), y, weights)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_combine(y, weights, plan)),
        rtol=1e-6, atol=1e-6)
    gy, gw = vjp(g_tokens)
    want_gy, want_gw = _ref_combine_bwd(y, weights, plan, g_tokens)
    np.testing.assert_allclose(np.asarray(gy), np.asarray(want_gy),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(gy)[n:].any()
    np.testing.assert_allclose(np.asarray(gw), np.asarray(want_gw),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["none", "one_row", "quarter", "all",
                                  "ragged", "bounded"])
def test_segment_sum_kernel_in_interpret_mode(case):
    """The kernel path of ``combine`` forward and ``dispatch`` backward
    (``held_rows_sum_tpu``: the matmul unit adds a tile's rows) against the
    plain formulas, bfloat16 rows as the kernel takes them."""
    from jax.experimental.pallas import tpu as pltpu
    tokens, top_k, width, plan = _held_plan(case, seed=1)
    total = plan.order.shape[0]
    k = jax.random.split(jax.random.PRNGKey(11), 3)
    valid, _ = _ref_masks(plan)
    y = jnp.where(valid, jax.random.normal(k[0], (total, width)),
                  0).astype(jnp.bfloat16)
    weights = jax.nn.softmax(jax.random.normal(k[1], (tokens, top_k)), -1)
    assert moe.permutation_lowering(True, y, tokens) == "pallas"
    assert moe.permutation_lowering(False, y, tokens) == "xla"
    assert moe.permutation_lowering(True, y.astype(jnp.float32),
                                    tokens) == "xla"
    with pltpu.force_tpu_interpret_mode():
        out = jax.jit(lambda y, w: moe.combine(y, w, plan, True))(y, weights)
        gx = jax.jit(lambda g: moe._dispatch_bwd(True, plan, g)[0])(y)
    # float32 sums before the one rounding to bfloat16: at most one unit of
    # bfloat16 apart where the order of the adds moved a float32 sum across
    # a rounding boundary
    for got, want in ((out, _ref_combine(y, weights, plan)),
                      (gx, _ref_dispatch_bwd(plan, y))):
        assert got.dtype == jnp.bfloat16
        got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-6)
        assert (got == want).mean() > 0.99


def test_no_array_of_every_assignments_row_exists_at_the_cells_shape():
    """``mellum2_train_seq8192``'s shape (8192 tokens, top-8, 2304 wide, a
    buffer of 65,536 rows): the four operators, as jit lowers them, hold no
    [T, top_k, D] array in any dtype: nothing gathers a row per assignment,
    held or not (float32, that array was 604 MB a layer)."""
    tokens, top_k, width, held = 8192, 8, 2304, 16
    total = tokens * top_k
    s = jax.ShapeDtypeStruct
    plan = moe.Plan(s((total,), jnp.int32), s((tokens, top_k), jnp.int32),
                    s((held,), jnp.int32))
    x = s((tokens, width), jnp.bfloat16)
    y = s((total, width), jnp.bfloat16)
    w = s((tokens, top_k), jnp.float32)
    lowered = {
        "dispatch": jax.jit(lambda x, p: moe.dispatch(x, p)).lower(x, plan),
        "dispatch_grad": jax.jit(
            lambda p, g: moe._dispatch_bwd(False, p, g)).lower(plan, y),
        "combine": jax.jit(
            lambda y, w, p: moe.combine(y, w, p)).lower(y, w, plan),
        "combine_grad": jax.jit(lambda y, w, p, g: moe._combine_bwd(
            False, (y, w, p), g)).lower(y, w, plan, x),
    }
    for name, low in lowered.items():
        text = low.as_text()
        assert f"{tokens}x{top_k}x{width}x" not in text, name
        # the three that read the buffer visit its held rows under a trip
        # count of the data's; ``dispatch`` writes the buffer in one gather
        assert ("stablehlo.while" in text) == (name != "dispatch"), name
