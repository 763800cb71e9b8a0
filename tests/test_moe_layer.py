"""The expert layer's two operators (`mxtpu/ops/moe.py`) against the plain
reference's expert layer (`benchmark/references/laguna.py`): the shares of
the result add up, nothing is dropped, the loads count the pairs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from benchmark.references import laguna
from mxtpu.ops import moe
from mxtpu.ops.registry import get_op

# the rehearsal's expert layer: 16 experts of width 32, 3 a token, a shared
# one of 32; a chip holds 4
CFG = {"hidden_size": 64, "router_num_experts": 16, "num_experts_per_tok": 3,
       "moe_intermediate_size": 32, "moe_routed_scaling_factor": 2.5}
TOKENS = 96


def _weights(seed=0, experts=16, d=64, f=32, std=0.3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = jax.random.normal
    return {"x": n(ks[0], (TOKENS, d)),
            "router_weight": n(ks[1], (experts, d)) * 0.5,
            "experts_gate_weight": n(ks[2], (experts, f, d)) * std,
            "experts_up_weight": n(ks[3], (experts, f, d)) * std,
            "experts_down_weight": n(ks[4], (experts, d, f)) * std,
            "shared_ff_gate_weight": n(ks[5], (f, d)) * std,
            "shared_ff_up_weight": n(ks[6], (f, d)) * std,
            "shared_ff_down_weight": n(ks[7], (d, f)) * std}


def _reference_layer(w, held, offset):
    """The plain reference's expert layer, shared expert included, holding
    experts offset .. offset + held - 1."""
    cfg = dict(CFG, num_experts=held, expert_offset=offset)
    lp = {k: (v[offset:offset + held] if k.startswith("experts_") else v)
          for k, v in w.items() if k != "x"}
    with jax.default_matmul_precision("highest"):
        return laguna._moe(w["x"], lp, cfg, lambda a: a, None)


def _program_share(w, held, offset, chunk=0, shared=False):
    """The two operators' share of the routed experts' result (and the
    shared expert's output, if asked), and the loads. `chunk`: rows a trip
    of the dispatch loop, so that these few pairs take several trips."""
    rows, moe.CHUNK = moe.CHUNK, chunk or moe.CHUNK
    try:
        return _share(w, held, offset, shared)
    finally:
        moe.CHUNK = rows


def _share(w, held, offset, shared):
    with jax.default_matmul_precision("highest"):
        tw, ti = moe._router_op(
            get_op("_contrib_MoERouter").parse_attrs(
                {"num_experts": 16, "top_k": 3, "scale": 2.5}),
            w["x"], w["router_weight"])
        cut = slice(offset, offset + held)
        out, loads = moe.moe_experts(
            w["x"], tw, ti, w["experts_gate_weight"][cut],
            w["experts_up_weight"][cut], w["experts_down_weight"][cut],
            16, offset)
        if shared:
            out = out + laguna._ffn(
                w["x"], w["shared_ff_gate_weight"], w["shared_ff_up_weight"],
                w["shared_ff_down_weight"], lambda a: a)
    return out, loads, ti


@pytest.mark.parametrize("chunk", [0, 16])
def test_the_shares_add_up_to_the_uncut_layer(chunk):
    """Four chips of 4 experts each (offsets 0, 4, 8, 12), the shared expert
    counted once, give what the uncut reference gives for the whole layer:
    forward and gradient."""
    w = _weights(3)
    cot = jax.random.normal(jax.random.PRNGKey(9), (TOKENS, 64))

    def whole(w):
        return jnp.sum(_reference_layer(w, 16, 0) * cot)

    def shares(w):
        total = sum(_program_share(w, 4, off, chunk, shared=off == 0)[0]
                    for off in (0, 4, 8, 12))
        return jnp.sum(total * cot)

    # jitted: op by op these few calls take the file's longest minute
    want, g_want = jax.jit(jax.value_and_grad(whole))(w)
    got, g_got = jax.jit(jax.value_and_grad(shares))(w)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda w: sum(
            _program_share(w, 4, off, chunk, shared=off == 0)[0]
            for off in (0, 4, 8, 12)))(w)),
        np.asarray(_reference_layer(w, 16, 0)), rtol=2e-5, atol=2e-5)
    for name in w:
        np.testing.assert_allclose(np.asarray(g_got[name]),
                                   np.asarray(g_want[name]),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("offset", [0, 4, 12])
def test_one_chips_share_is_the_references_share(offset):
    w = _weights(5)
    got, loads, ti = _program_share(w, 4, offset, shared=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_reference_layer(w, 4, offset)),
                               rtol=2e-5, atol=2e-5)
    # the loads sum to the pairs routed here, expert by expert
    ti = np.asarray(ti)
    want = [(ti == offset + e).sum() for e in range(4)]
    assert list(np.asarray(loads)) == want
    assert loads.dtype == jnp.int32


def test_nothing_is_dropped_when_every_token_takes_one_expert():
    """A router forced to send every token to held expert 2 (and to two
    experts held elsewhere): all 96 pairs land in one group, none is dropped
    or padded in, and the result is the reference's; several trips of the
    dispatch loop."""
    w = _weights(7)
    bias = np.zeros((16, 64), np.float32)
    w["x"] = w["x"].at[:, 0].set(40.0)      # one loud channel
    bias[2, 0] = bias[9, 0] = bias[13, 0] = 1.0
    w["router_weight"] = w["router_weight"] * 0.01 + jnp.asarray(bias)
    got, loads, ti = _program_share(w, 4, 0, chunk=32, shared=True)
    assert sorted(np.unique(np.asarray(ti))) == [2, 9, 13]
    assert list(np.asarray(loads)) == [0, 0, TOKENS, 0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_reference_layer(w, 4, 0)),
                               rtol=1e-4, atol=1e-3)   # outputs in the 100s
    # and a token with no expert here gets the shared expert's output alone
    alone, loads, _ = _program_share(w, 4, 4, shared=False)
    assert int(np.asarray(loads).sum()) == 0
    assert float(jnp.max(jnp.abs(alone))) == 0.0


def test_the_router_is_the_references():
    w = _weights(11)
    cfg = dict(CFG, num_experts=4, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        want_w, want_i = laguna.route(w["x"], w["router_weight"], cfg)
        got_w, got_i = moe.route(w["x"] @ w["router_weight"].T, 3, 2.5)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_w).sum(-1), 2.5, rtol=1e-6)
    # ties go to the lower index
    _, tie = moe.route(jnp.zeros((2, 8)), 3)
    assert np.asarray(tie).tolist() == [[0, 1, 2], [0, 1, 2]]
    # bfloat16 activations still score in float32
    tw, _ = moe._router_op(
        get_op("_contrib_MoERouter").parse_attrs(
            {"num_experts": 16, "top_k": 3}),
        w["x"].astype(jnp.bfloat16), w["router_weight"])
    assert tw.dtype == jnp.float32


def test_the_operators_as_a_symbol_with_loads_beside_the_output():
    """Through the Symbol: weights inferred from the attributes, the loads a
    second, integer head that takes no gradient."""
    x = mx.sym.Variable("data")
    r = mx.sym.contrib.MoERouter(x, num_experts=16, top_k=3, scale=2.5,
                                 name="router")
    e = mx.sym.contrib.MoEExperts(x, r[0], r[1], num_experts=16,
                                  experts_held=4, hidden=32, expert_offset=4,
                                  name="experts")
    net = mx.sym.Group([mx.sym.MakeLoss(mx.sym.sum(e[0])), e[1]])
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=(2, 48, 64))[0]))
    assert shapes == {"data": (2, 48, 64), "router_weight": (16, 64),
                      "experts_gate_weight": (4, 32, 64),
                      "experts_up_weight": (4, 32, 64),
                      "experts_down_weight": (4, 64, 32)}
    w = _weights(13)
    args = {"data": mx.nd.NDArray(w["x"].reshape(2, 48, 64)),
            "router_weight": mx.nd.NDArray(w["router_weight"])}
    for k in ("gate", "up", "down"):
        args["experts_%s_weight" % k] = mx.nd.NDArray(
            w["experts_%s_weight" % k][4:8])
    grads = {k: mx.nd.zeros(v.shape) for k, v in args.items()}
    ex = net.bind(mx.cpu(), args, args_grad=grads)
    outs = ex.forward(is_train=True)
    ex.backward()
    _, loads, ti = _program_share(w, 4, 4)
    assert outs[1].asnumpy().tolist() == np.asarray(loads).tolist()
    assert str(outs[1].dtype) == "int32"
    assert float(np.abs(grads["experts_down_weight"].asnumpy()).sum()) > 0
    assert float(np.abs(grads["router_weight"].asnumpy()).sum()) > 0


def test_the_fetched_loads_are_counted():
    from mxtpu import telemetry

    def value(name):
        return [m.value for m in telemetry.registry().series()
                if m.name == name][0]

    w = _weights(17)
    _program_share(w, 4, 0)     # traces a layer of 96 tokens
    moe.observe_loads([np.array([1, 2, 3, 2])])      # the series exist
    pairs, seen = value("moe_pairs_routed"), value("moe_tokens_seen")
    moe.observe_loads([np.array([10, 30, 20, 20]), np.array([24, 24, 24, 24])])
    assert value("moe_pairs_routed") == pairs + 176
    assert value("moe_tokens_seen") == seen + 2 * TOKENS
    assert value("moe_load_max_over_mean") == pytest.approx(1.5)
    assert value("moe_experts_held") == 4
    assert value("moe_dispatch_rows_bound") == TOKENS * 3


def test_trips_behind_the_first_are_counted_from_the_loads():
    """`observe_loads` counts the layers of the steps fetched and the trips
    their dispatch took behind the first (which runs outside the loop): the
    loads in whole tiles over the rows a trip of the last layer traced."""
    from mxtpu import telemetry

    def value(name):
        return [m.value for m in telemetry.registry().series()
                if m.name == name][0]

    _program_share(_weights(17), 4, 0)   # 96 tokens: trips of 384 rows
    assert moe._last_traced == (TOKENS, 128, 384)      # in tiles of 128
    moe.observe_loads([np.array([0, 0, 0, 0])])        # the series exist
    steps, trips = (value("moe_layer_steps_seen"),
                    value("moe_trips_after_first"))
    # 3 tiles: one trip; 4 tiles: a second; none: the first trip alone;
    # 129 pairs take two tiles, so 7 tiles in the last: a third trip
    moe.observe_loads([np.array([128, 1, 0, 5]), np.array([10, 30, 20, 20]),
                       np.array([0, 0, 0, 0]), np.array([129, 128, 130, 200])])
    assert value("moe_layer_steps_seen") == steps + 4
    assert value("moe_trips_after_first") == trips + 0 + 1 + 0 + 2


# ------------------------------------ every gradient against a dense loop
FORMS = {"gated": (moe._silu_gated, 2), "relu2": (moe._relu2, 1)}


def _dense(x, tw, ti, ups, wd, offset, act):
    """A dense loop over the experts held: each over all tokens, times the
    weight the router gave it (0 where not chosen)."""
    out = 0.0
    for e in range(wd.shape[0]):
        mine = jnp.sum(jnp.where(ti == offset + e, tw, 0.0), axis=-1)
        h = act(*[x @ u[e].T for u in ups])
        out = out + mine[:, None] * (h @ wd[e].T)
    return out


# case: (the experts its tokens choose among, rows a trip if not `CHUNK`'s,
# the held experts (4..7 as 0..3) that so get no rows). 40 tokens: a held
# expert with rows fills one tile of 128, and 128 rows a trip make it a trip
CASES = {
    "one_trip": (range(16), 0, []),
    "four_trips": (range(2, 10), 128, []),
    "three_trips_round_an_expert_with_no_rows": ([0, 1, 4, 6, 7, 9], 128,
                                                 [1]),
    "an_expert_with_no_rows": ([0, 1, 4, 6, 7, 9], 0, [1]),
    # a share that is handed no pair at all: the first trip runs on nothing
    "no_pair": (range(8, 16), 0, [0, 1, 2, 3]),
    # a chosen weight that is exactly 0 still takes its gradient
    "a_weight_of_zero": (range(4, 8), 0, []),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("form", list(FORMS))
def test_every_gradient_is_the_dense_loops(form, case):
    """x, `topk_weight` (the router's way in), and every stacked leaf, for
    experts 4..7 of 16 held: the peeled first trip, the loop behind it, and
    both with nothing to do."""
    act, n_ups = FORMS[form]
    among, chunk, empty = CASES[case]
    among = np.asarray(list(among))
    n_tok, d, f, k, held, offset = 40, 16, 8, 3, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(41), 7)
    # k distinct experts a token, of those the case allows
    ti = jnp.asarray(among)[jnp.argsort(jax.random.uniform(
        ks[0], (n_tok, len(among))), axis=-1)[:, :k]].astype(jnp.int32)
    tw = jax.random.uniform(ks[1], (n_tok, k), minval=0.2, maxval=1.0)
    if case == "a_weight_of_zero":
        tw = tw.at[0, 0].set(0.0).at[7, 2].set(0.0)
    args = {"x": jax.random.normal(ks[2], (n_tok, d)), "tw": tw,
            "ups": tuple(jax.random.normal(ks[3 + i], (held, f, d)) * 0.3
                         for i in range(n_ups)),
            "wd": jax.random.normal(ks[5], (held, d, f)) * 0.3}
    cot = jax.random.normal(ks[6], (n_tok, d))

    def program(a):
        out, loads = moe.moe_experts(
            a["x"], a["tw"], ti, a["ups"][0] if n_ups == 2 else None,
            a["ups"][-1], a["wd"], 16, offset)
        return jnp.sum(out * cot), loads

    def dense(a):
        return jnp.sum(_dense(a["x"], a["tw"], ti, a["ups"], a["wd"], offset,
                              act) * cot)

    rows, moe.CHUNK = moe.CHUNK, chunk or moe.CHUNK
    try:
        with jax.default_matmul_precision("highest"):
            (got, loads), g_got = jax.jit(jax.value_and_grad(
                program, has_aux=True))(args)
            want, g_want = jax.jit(jax.value_and_grad(dense))(args)
    finally:
        moe.CHUNK = rows
    loads = np.asarray(loads).tolist()
    assert loads == [int((np.asarray(ti) == offset + e).sum())
                     for e in range(held)]
    assert [e for e in range(held) if not loads[e]] == empty, loads
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5, atol=2e-5)
    flat_got, tree = jax.tree_util.tree_flatten(g_got)
    flat_want, tree_want = jax.tree_util.tree_flatten(g_want)
    assert tree == tree_want
    for got_leaf, want_leaf in zip(flat_got, flat_want):
        assert got_leaf.dtype == want_leaf.dtype
        np.testing.assert_allclose(np.asarray(got_leaf),
                                   np.asarray(want_leaf),
                                   rtol=2e-4, atol=2e-4)
    if case == "no_pair":
        assert all(float(jnp.abs(v).max()) == 0.0 for v in flat_got)
    else:
        assert all(float(jnp.abs(v).sum()) > 0 for v in flat_want)
    if case == "a_weight_of_zero":
        assert float(jnp.abs(g_want["tw"][0, 0])) > 1e-3
        assert float(jnp.abs(g_want["tw"][7, 2])) > 1e-3


# ------------------------------------------------------------- the combine
# case: (the experts its 64 tokens choose among, 3 each, of which 4..7 are
# held; rows a trip, if not all of the plan's rows)
COMBINES = {
    "several_pairs_a_token": (range(3, 9), 0),
    "dead_rows_in_a_tile": (range(16), 0),
    "an_expert_with_no_pairs": ([0, 1, 4, 6, 7, 9], 0),
    "no_pair_held": (range(8, 16), 0),
    "a_second_trip": (range(2, 10), 128),
}


@pytest.mark.parametrize("case", list(COMBINES))
@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_the_combine_is_the_scatter_add(path, case, monkeypatch):
    """The combine of every trip's float32 rows, the kernel (in interpret
    mode) and the plain body, against `.at[tok].add` of the same rows into
    the tokens' rows, with the pairs' weights (the forward) and without (the
    backward's dx): each token's pairs summed in float32 and nothing else; a
    row that holds no pair (NaN here) adds nothing; one trip is written in
    the output's dtype, several summed in float32 first."""
    among, chunk = COMBINES[case]
    n, k, held, offset, d, tile = 64, 3, 4, 4, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    ti = jnp.asarray(np.asarray(list(among)))[jnp.argsort(jax.random.uniform(
        ks[0], (n, len(among))), axis=-1)[:, :k]].astype(jnp.int32)
    tw = jax.random.uniform(ks[1], (n, k), minval=0.2, maxval=1.0)
    order, ends, loads = moe._plan_tiled(ti, held, offset, tile)
    bounds = moe._bounds(ti, held, offset, ends, 32)
    chunk = chunk or int(max(ends[-1], tile))
    trips, rows = moe._walk_tiled(order, ends, loads, k, chunk)
    trips = max(int(trips), 1)      # the first trip is always taken
    assert (trips > 1) == (case == "a_second_trip"), trips
    held_pairs = ((ti >= offset) & (ti < offset + held)).sum(axis=1)
    if case == "several_pairs_a_token":
        assert int(held_pairs.max()) == k
    if case == "an_expert_with_no_pairs":
        assert 0 in np.asarray(loads).tolist()
    if case == "no_pair_held":
        assert int(held_pairs.max()) == 0
    walked = [rows(c) for c in range(trips)]
    assert any(not bool(live.all()) for _, _, live, _ in walked)  # dead rows
    got_rows = jnp.stack([jnp.where(live[:, None], jax.random.normal(
        jax.random.fold_in(ks[2], c), (chunk, d)), jnp.nan)
        for c, (_, _, live, _) in enumerate(walked)])
    tokens = None
    if path == "kernel":
        call, tokens = moe._combine_call, 32
        monkeypatch.setattr(moe, "_combine_call", lambda *a: call(
            *a, interpret=True))
        monkeypatch.setattr(moe, "_lowered",
                            lambda tiles, kernel, plain, *a: kernel(*a))
    for weighted in (True, False):
        want = jnp.zeros((n, d), jnp.float32)
        for c, (pairs, tok, live, _) in enumerate(walked):
            y = got_rows[c] * (tw.reshape(-1)[pairs][:, None]
                               if weighted else 1.0)
            want = want.at[tok].add(jnp.where(live[:, None], y, 0.0))
        pairs_of = jnp.stack([p for p, _, _, _ in walked])
        toks = jnp.stack([t for _, t, _, _ in walked])
        lives = jnp.stack([v for _, _, v, _ in walked])

        def trip(c, carry):
            w = tw.reshape(-1)[pairs_of[c]] if weighted else None
            return (got_rows[c], toks[c], w, lives[c]), carry + 1

        out, carry = moe._combined(trips, trip, 0, bounds, chunk, n,
                                   jnp.bfloat16, tokens)
        assert int(carry) == trips and out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(want.astype(jnp.bfloat16), np.float32),
            rtol=1e-2, atol=1e-2)
        # in float32, to the rounding of a sum of at most k rows
        out32, _ = moe._combined(trips, trip, 0, bounds, chunk, n,
                                 jnp.float32, tokens)
        np.testing.assert_allclose(np.asarray(out32), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        if case == "no_pair_held":
            assert float(jnp.abs(out32).max()) == 0.0


# ------------------------------------------ the ungated squared-ReLU form
# the Nemotron-H rehearsal's expert layer: 16 experts of width 32, 3 a
# token, a shared one of 64; 16 shares of ONE expert each add up below
NCFG = {"router_num_experts": 16, "num_experts_per_tok": 3,
        "routed_scaling_factor": 2.5}


def _relu2_weights(seed):
    w = _weights(seed)
    del w["experts_gate_weight"], w["shared_ff_gate_weight"]
    return w


def _relu2_dense(w, held, offset, shared=True):
    """relu(x W_up^T)^2 W_down^T, the dense loop over the experts held
    (`_dense`) behind the router, beside the shared expert if asked."""
    with jax.default_matmul_precision("highest"):
        tw, ti = moe.route(w["x"] @ w["router_weight"].T, 3, 2.5)
        cut = slice(offset, offset + held)
        out = _dense(w["x"], tw, ti, (w["experts_up_weight"][cut],),
                     w["experts_down_weight"][cut], offset, moe._relu2)
        if shared:
            out = out + jnp.square(jax.nn.relu(
                w["x"] @ w["shared_ff_up_weight"].T)) @ \
                w["shared_ff_down_weight"].T
    return out


def _relu2_share(w, held, offset, chunk=0):
    rows, moe.CHUNK = moe.CHUNK, chunk or moe.CHUNK
    try:
        with jax.default_matmul_precision("highest"):
            tw, ti = moe.route(w["x"] @ w["router_weight"].T, 3, 2.5)
            cut = slice(offset, offset + held)
            return moe.moe_experts(w["x"], tw, ti, None,
                                   w["experts_up_weight"][cut],
                                   w["experts_down_weight"][cut], 16, offset)
    finally:
        moe.CHUNK = rows


@pytest.mark.parametrize("held,chunk", [(4, 16), (16, 0)])
def test_relu2_experts_are_a_dense_loop_over_the_experts(held, chunk):
    """Forward and every gradient (x, the router through the weights, both
    stacked leaves), in one trip and in several."""
    w = _relu2_weights(21)
    cot = jax.random.normal(jax.random.PRNGKey(5), (TOKENS, 64))
    got, loads = _relu2_share(w, held, 4 if held == 4 else 0, chunk)
    offset = 4 if held == 4 else 0
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_relu2_dense(w, held, offset, False)),
        rtol=2e-5, atol=2e-5)
    assert int(np.asarray(loads).sum()) > 0 and loads.dtype == jnp.int32
    g_got = jax.grad(lambda w: jnp.sum(
        _relu2_share(w, held, offset, chunk)[0] * cot))(w)
    g_want = jax.grad(lambda w: jnp.sum(
        _relu2_dense(w, held, offset, False) * cot))(w)
    for name in ("x", "router_weight", "experts_up_weight",
                 "experts_down_weight"):
        assert float(jnp.abs(g_want[name]).sum()) > 0, name
        np.testing.assert_allclose(np.asarray(g_got[name]),
                                   np.asarray(g_want[name]),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_the_sixteen_relu2_shares_add_up_to_the_uncut_reference_layer():
    """All 16 shares of one expert each (expert_offset 0, 1, ..., 15: at the
    cell's size 0, 8, ..., 120 of 8 each), the shared expert counted once,
    give what the uncut plain reference (benchmark/references/nemotron_h.py)
    gives for the whole layer: forward and gradient."""
    from benchmark.references import nemotron_h
    w = _relu2_weights(23)
    cot = jax.random.normal(jax.random.PRNGKey(6), (TOKENS, 64))

    def whole(w):
        cfg = dict(NCFG, n_routed_experts=16, expert_offset=0)
        lp = {k: v for k, v in w.items() if k != "x"}
        with jax.default_matmul_precision("highest"):
            return nemotron_h._moe(w["x"], lp, cfg, lambda a: a, None)

    def shares(w):
        with jax.default_matmul_precision("highest"):
            once = jnp.square(jax.nn.relu(
                w["x"] @ w["shared_ff_up_weight"].T)) @ \
                w["shared_ff_down_weight"].T
        return once + sum(_relu2_share(w, 1, off)[0] for off in range(16))

    np.testing.assert_allclose(np.asarray(jax.jit(shares)(w)),
                               np.asarray(whole(w)), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(whole(w)),
                               np.asarray(_relu2_dense(w, 16, 0)),
                               rtol=2e-5, atol=2e-5)
    pairs = sum(int(v) for v in jax.jit(lambda w: [
        _relu2_share(w, 1, off)[1][0] for off in range(16)])(w))
    assert pairs == TOKENS * 3          # every pair lands on one share
    g_want = jax.jit(jax.grad(lambda w: jnp.sum(whole(w) * cot)))(w)
    g_got = jax.jit(jax.grad(lambda w: jnp.sum(shares(w) * cot)))(w)
    for name in w:
        np.testing.assert_allclose(np.asarray(g_got[name]),
                                   np.asarray(g_want[name]),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_the_relu2_operator_takes_two_stacked_leaves():
    """Through the Symbol: no gate leaf, shapes from the attributes; the
    gated form's arguments are what they were; an unknown form is refused."""
    x = mx.sym.Variable("data")
    r = mx.sym.contrib.MoERouter(x, num_experts=16, top_k=3, scale=2.5,
                                 name="router")

    def experts(**kw):
        return mx.sym.contrib.MoEExperts(
            x, r[0], r[1], num_experts=16, experts_held=4, hidden=32,
            expert_offset=4, name="experts", **kw)

    e = experts(activation="relu2")
    assert e.list_arguments() == ["data", "router_weight",
                                  "experts_up_weight", "experts_down_weight"]
    shapes = dict(zip(e.list_arguments(),
                      e.infer_shape(data=(2, 48, 64))[0]))
    assert shapes["experts_up_weight"] == (4, 32, 64)
    assert shapes["experts_down_weight"] == (4, 64, 32)
    assert experts().list_arguments() == [
        "data", "router_weight", "experts_gate_weight", "experts_up_weight",
        "experts_down_weight"]
    assert "activation" not in experts().tojson()
    w = _relu2_weights(25)
    args = {"data": mx.nd.NDArray(w["x"].reshape(2, 48, 64)),
            "router_weight": mx.nd.NDArray(w["router_weight"]),
            "experts_up_weight": mx.nd.NDArray(w["experts_up_weight"][4:8]),
            "experts_down_weight": mx.nd.NDArray(w["experts_down_weight"][4:8])}
    outs = e.bind(mx.cpu(), args).forward(is_train=False)
    want, loads = _relu2_share(w, 4, 4)
    np.testing.assert_allclose(outs[0].asnumpy().reshape(TOKENS, 64),
                               np.asarray(want), rtol=1e-4, atol=1e-4)
    assert outs[1].asnumpy().tolist() == np.asarray(loads).tolist()
    moe.observe_loads([np.asarray(loads)])      # feeds the same counters
    with pytest.raises(Exception):
        experts(activation="gelu").infer_shape(data=(2, 48, 64))


@pytest.mark.parametrize("form", list(FORMS))
def test_experts_on_tiles_of_their_own_and_at_a_padded_width(form):
    """The one plan, for either form, gives every held expert rows of its
    own in whole tiles, and a width over one tile is padded with zeros to
    whole tiles: neither moves the result or a gradient, nothing is dropped,
    and the padding takes no gradient."""
    act, n_ups = FORMS[form]
    ks = jax.random.split(jax.random.PRNGKey(31), 6)
    n_tok, d, f = 160, 32, 576          # 576 -> 1024 columns
    w = {"x": jax.random.normal(ks[0], (n_tok, d)),
         "router_weight": jax.random.normal(ks[1], (16, d)) * 0.5,
         "ups": tuple(jax.random.normal(ks[2 + i], (16, f, d)) * 0.2
                      for i in range(n_ups)),
         "experts_down_weight": jax.random.normal(ks[4], (16, d, f)) * 0.2}
    cot = jax.random.normal(ks[5], (n_tok, d))
    *wide, wide_d = moe._widened(tuple(u[:4] for u in w["ups"])
                                 + (w["experts_down_weight"][:4],))
    assert [u.shape for u in wide] == [(4, 1024, d)] * n_ups
    assert wide_d.shape == (4, d, 1024)
    # a width of whole tiles (Laguna's 1024), or under one, is left alone
    for width in (1024, 32):
        leaves = (jnp.zeros((4, width, d)),) * n_ups + (
            jnp.zeros((4, d, width)),)
        assert moe._widened(leaves) is leaves

    def share(w):
        with jax.default_matmul_precision("highest"):
            tw, ti = moe.route(w["x"] @ w["router_weight"].T, 3, 2.5)
            ups = tuple(u[4:8] for u in w["ups"])
            return moe.moe_experts(w["x"], tw, ti,
                                   ups[0] if n_ups == 2 else None, ups[-1],
                                   w["experts_down_weight"][4:8], 16, 4)

    def dense(w):
        with jax.default_matmul_precision("highest"):
            tw, ti = moe.route(w["x"] @ w["router_weight"].T, 3, 2.5)
            return _dense(w["x"], tw, ti, tuple(u[4:8] for u in w["ups"]),
                          w["experts_down_weight"][4:8], 4, act)

    (_, (got, loads)), g_got = jax.jit(jax.value_and_grad(
        lambda w: (jnp.sum(share(w)[0] * cot), share(w)), has_aux=True))(w)
    want, g_want = jax.jit(lambda w: (dense(w), jax.grad(
        lambda w: jnp.sum(dense(w) * cot))(w)))(w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    for got_leaf, want_leaf in zip(jax.tree_util.tree_leaves(g_got),
                                   jax.tree_util.tree_leaves(g_want)):
        assert float(jnp.abs(want_leaf).sum()) > 0
        np.testing.assert_allclose(np.asarray(got_leaf),
                                   np.asarray(want_leaf),
                                   rtol=5e-4, atol=5e-4)
    # the plan: every expert's rows start at a multiple of the tile, every
    # pair routed here has one row, no row holds a pair routed elsewhere,
    # none past the last expert's tiles holds any
    _, ti = moe.route(w["x"] @ w["router_weight"].T, 3, 2.5)
    order, ends, planned = moe._plan_tiled(ti, 4, 4, 128)
    slots = np.asarray(moe._slots(order, ends, planned,
                                  jnp.arange(n_tok * 3 + 4 * 128)))
    ends = np.asarray(ends)
    assert planned.tolist() == np.asarray(loads).tolist()
    assert (ends % 128 == 0).all() and ends[-1] <= slots.shape[0]
    held = slots[slots < n_tok * 3]
    assert sorted(held) == sorted(np.flatnonzero(
        (np.asarray(ti).reshape(-1) >= 4) & (np.asarray(ti).reshape(-1) < 8)))
    start = 0
    for e, end in enumerate(ends):
        rows = slots[start:end]
        rows = rows[rows < n_tok * 3]
        assert (np.asarray(ti).reshape(-1)[rows] == 4 + e).all()
        assert len(rows) == planned[e]
        start = end
    assert (slots[start:] == n_tok * 3).all()
