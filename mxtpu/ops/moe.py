"""A mixture-of-experts layer as two operators: the router, and the part of
the layer's result that the experts held here give.

``_contrib_MoERouter(data, weight)``: scores in float32 whatever `data` is
(rho = x W_r^T at `highest` precision, then a sigmoid or a softmax), the
`top_k` largest over ALL `num_experts` (ties to the lower index), their
weights normalised over the chosen and scaled: w_e = scale * s_e / sum of
the chosen s. Outputs (weights (.., k) float32, indices (.., k) int32).
`route` is the function; `mxtpu.parallel.moe` takes its choices from it too.

``_contrib_MoEExperts(data, topk_weight, topk_index, gate_weight,
up_weight, down_weight)``: told `num_experts`, `experts_held` and
`expert_offset`, it holds experts offset .. offset + held - 1 as three
stacked leaves ((held, f, d), (held, f, d), (held, d, f), each expert
(silu(x W_gate^T) * x W_up^T) W_down^T) and returns sum over the chosen
experts held here of w_e E_e(x). What the other experts would have added
is left out (the expert-parallel share of the result; on one chip the layer
runs without its exchange). A second output is the pairs each held expert
received, (held,) int32, which takes no gradient. With
``activation="relu2"`` an expert is ungated, relu(x W_up^T)^2 W_down^T, and
the operator takes two stacked leaves (``up_weight``, ``down_weight``)
through the same loop and grouped products; its plan gives every held
expert rows of its own in whole tiles (`_plan_tiled`) and its products run
at a width of whole tiles (`_widened`), so that a trip's time does not
follow where the groups' boundaries fall. What calls for the tiles is the
grouped product's geometry, not the activation: the gated form keeps the
packed plan because its programs were held to what they were (PR 34), and
one plan for both is ROADMAP Speed 10 (a).

Dispatch is dropless by construction. The (token, slot) pairs are sorted
by expert (a stable argsort of the local expert index, pairs routed
elsewhere last), and a loop walks the sorted pairs `chunk` rows at a time
for as many trips as the pairs held here need: gather the rows' tokens, a
grouped product over the chunk's ragged groups (`jax.lax.ragged_dot_general`,
which XLA lowers to a Mosaic grouped matmul on the TPU) for gate, up and
down, weight, scatter-add into the tokens' rows. Nothing has a capacity, so
nothing overflows; the static bound is the pairs' array itself (tokens x
top_k int32) and one chunk of activations, and **time follows the pairs
routed here**, not tokens x experts held. The loop's trip count depends on
the data, so the layer brings its own gradient (``jax.custom_vjp``): the
same walk again, recomputing gate and up (the up product alone in the
ungated form), with the weight gradients accumulated in float32 by grouped
products whose ragged dimension is the contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from .registry import Required, register

_F32 = jnp.float32
CHUNK = 4096        # rows a trip of the dispatch loop, at most
TILE = 512          # rows and columns a tile of XLA's grouped matmul (TPU)

# tokens one call of the last expert layer traced takes: what the fetched
# loads are counted against (`observe_loads`)
_tokens_last_traced = 0


# ------------------------------------------------------------------ router
def route(logits, top_k, scale=1.0, score_func="sigmoid", norm_topk=True):
    """(weights (N, k) float32, indices (N, k) int32) of float32 router
    logits (N, E): the `top_k` largest scores of each row, ties to the lower
    index, normalised over the chosen (`norm_topk`) and scaled."""
    logits = logits.astype(_F32)
    if score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError("route: unknown score_func %r" % (score_func,))
    top, index = jax.lax.top_k(scores, int(top_k))
    if norm_topk:
        top = top / jnp.maximum(jnp.sum(top, axis=-1, keepdims=True), 1e-20)
    return top * scale, index.astype(jnp.int32)


def _router_op(a, x, weight):
    lead = x.shape[:-1]
    logits = jnp.einsum("nd,ed->ne", x.reshape(-1, x.shape[-1]).astype(_F32),
                        weight.astype(_F32),
                        precision=jax.lax.Precision.HIGHEST)
    telemetry.gauge("moe_experts_total", help="experts the last router "
                    "traced scores").set(logits.shape[-1])
    telemetry.gauge("moe_top_k", help="experts a token of the last router "
                    "traced").set(a.top_k)
    w, i = route(logits, a.top_k, a.scale, a.score_func, a.norm_topk)
    return w.reshape(lead + (a.top_k,)), i.reshape(lead + (a.top_k,))


def _router_args(a, shapes):
    data = shapes[0]
    return [data, (a.num_experts, data[-1]) if data else shapes[1]]


register("_contrib_MoERouter", _router_op, arg_names=["data", "weight"],
         attrs={"num_experts": Required(int), "top_k": Required(int),
                "scale": 1.0, "score_func": "sigmoid", "norm_topk": True},
         num_outputs=2, infer_args=_router_args, aliases=("moe_router",))


# ----------------------------------------------------------------- experts
def _grouped(lhs, rhs, sizes, mode):
    """A grouped product over the ragged groups `sizes` of lhs's rows,
    float32 out. "nn": (m, K) x (g, K, N) -> (m, N); "tn": (m, A) x (m, B)
    -> (g, A, B), the groups the contraction. These two XLA lowers to a
    Mosaic grouped matmul on the TPU; a right-hand side contracted over its
    last dim it expands into a dense product over every group (compiled
    for a described v5e, PR 32), so the weights are turned instead
    (`_turned`)."""
    dims = {"nn": ((((1,), (1,)), ((), ())), [0], [0]),
            "tn": ((((0,), (0,)), ((), ())), [0], [])}[mode]
    return jax.lax.ragged_dot_general(
        lhs, rhs, sizes,
        jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=dims[0], lhs_ragged_dimensions=dims[1],
            rhs_group_dimensions=dims[2]),
        preferred_element_type=_F32)


def _plan(index, held, offset):
    """Sorts the (token, slot) pairs by the expert held here that they
    name: (order (N k,) pair ids, pairs routed elsewhere last; ends (held,)
    the running sum of the loads; loads (held,))."""
    key, order, loads = _sorted_pairs(index, held, offset)
    return order, jnp.cumsum(loads), loads


def _sorted_pairs(index, held, offset):
    """(key (N k,), the held expert each pair names, `held` for one routed
    elsewhere; order, the pair ids sorted by it, stably; loads (held,))."""
    local = index.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    loads = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    return key, order, loads


def _walk(x, order, ends, k, chunk):
    """What every trip of the dispatch loop starts from: `trips`, and
    `rows(c)` -> (pair ids, their tokens, which rows hold a pair, the
    chunk's group sizes)."""
    total = ends[-1]
    pad = -order.shape[0] % chunk
    order = jnp.pad(order, (0, pad + chunk))
    before = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])

    def rows(c):
        start = c * chunk
        pairs = jax.lax.dynamic_slice(order, (start,), (chunk,))
        live = start + jnp.arange(chunk, dtype=jnp.int32) < total
        sizes = (jnp.clip(ends - start, 0, chunk)
                 - jnp.clip(before - start, 0, chunk)).astype(jnp.int32)
        return pairs, pairs // k, live, sizes

    return (total + chunk - 1) // chunk, rows


def _up(xs, sizes, live, *turned):
    """x W^T of a chunk's rows for each of the turned weights (gate and up,
    or up alone), 0 in rows that hold no pair: selects, not products, for
    rows past the last pair are in no group and hold whatever the grouped
    product left there."""
    return tuple(jnp.where(live[:, None], _grouped(xs, t, sizes, "nn"), 0.0)
                 for t in turned)


def _turned(*weights):
    """Stacked (held, out, in) leaves as (held, in, out), once a call."""
    return tuple(jnp.swapaxes(w, 1, 2) for w in weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _experts(x, w, order, ends, wg, wu, wd, k, chunk):
    trips, rows = _walk(x, order, ends, k, chunk)
    w_flat = w.reshape(-1)
    wg_t, wu_t, wd_t = _turned(wg, wu, wd)

    def trip(c, out):
        pairs, tok, live, sizes = rows(c)
        a, b = _up(x[tok], sizes, live, wg_t, wu_t)
        h = (jax.nn.silu(a) * b).astype(x.dtype)
        y = _grouped(h, wd_t, sizes, "nn") * w_flat[pairs][:, None]
        return out.at[tok].add(jnp.where(live[:, None], y, 0.0))

    out = jax.lax.fori_loop(0, trips, trip, jnp.zeros(x.shape, _F32))
    return out.astype(x.dtype)


def _experts_fwd(x, w, order, ends, wg, wu, wd, k, chunk):
    return (_experts(x, w, order, ends, wg, wu, wd, k, chunk),
            (x, w, order, ends, wg, wu, wd))


def _experts_bwd(k, chunk, res, g):
    x, w, order, ends, wg, wu, wd = res
    with telemetry.span("moe.build", category="compile",
                        tags={"pass": "bwd"}):
        trips, rows = _walk(x, order, ends, k, chunk)
        w_flat = w.reshape(-1)
        wg_t, wu_t, wd_t = _turned(wg, wu, wd)

        def trip(c, carry):
            dx, dw, dwg, dwu, dwd = carry
            pairs, tok, live, sizes = rows(c)
            xs = x[tok]
            a, b = _up(xs, sizes, live, wg_t, wu_t)
            sa = jax.nn.sigmoid(a)
            act = a * sa
            h = (act * b).astype(x.dtype)
            gy = jnp.where(live[:, None], g[tok].astype(_F32), 0.0)
            y = _grouped(h, wd_t, sizes, "nn")
            dw = dw.at[pairs].add(
                jnp.where(live, jnp.sum(gy * y, axis=-1), 0.0))
            dy = (gy * w_flat[pairs][:, None]).astype(x.dtype)
            dh = jnp.where(live[:, None], _grouped(dy, wd, sizes, "nn"), 0.0)
            dwd = dwd + _grouped(dy, h, sizes, "tn")
            da = (dh * b * (sa + act * (1.0 - sa))).astype(x.dtype)
            db = (dh * act).astype(x.dtype)
            dwg = dwg + _grouped(da, xs, sizes, "tn")
            dwu = dwu + _grouped(db, xs, sizes, "tn")
            dxs = _grouped(da, wg, sizes, "nn") + _grouped(db, wu, sizes,
                                                           "nn")
            dx = dx.at[tok].add(jnp.where(live[:, None], dxs, 0.0))
            return dx, dw, dwg, dwu, dwd

        dx, dw, dwg, dwu, dwd = jax.lax.fori_loop(
            0, trips, trip,
            (jnp.zeros(x.shape, _F32), jnp.zeros(w_flat.shape, _F32),
             jnp.zeros(wg.shape, _F32), jnp.zeros(wu.shape, _F32),
             jnp.zeros(wd.shape, _F32)))
    zero = functools.partial(np.zeros, dtype=jax.dtypes.float0)
    return (dx.astype(x.dtype), dw.reshape(w.shape).astype(w.dtype),
            zero(order.shape), zero(ends.shape), dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), dwd.astype(wd.dtype))


_experts.defvjp(_experts_fwd, _experts_bwd)


def _plan_tiled(index, held, offset, tile):
    """`_plan` with every held expert's pairs starting at a multiple of
    `tile` rows: (slots, pair ids by row with `tokens x k` where a row holds
    none; ends (held,), the running sum of the loads rounded up to whole
    tiles; loads (held,)). XLA's grouped matmul on the TPU walks the rows in
    tiles of 512 and takes a tile once for every group that has rows in it
    (0.16 ms a visit at the Nemotron cell's sizes, my chip run, PR 34): with
    the groups packed end to end the visits, and so the time, follow where
    the boundaries happen to fall; with each group on tiles of its own they
    are the experts' own tiles and no more. Six seeds of that cell spread by
    0.46% packed end to end at the padded width and by 0.17% on tiles of
    their own, at the same median step (my chip runs, PR 34)."""
    key, order, loads = _sorted_pairs(index, held, offset)
    room = -(-loads // tile) * tile
    ends = jnp.cumsum(room)
    mine = jnp.minimum(key[order], held - 1)
    rank = jnp.arange(order.shape[0], dtype=jnp.int32) \
        - (jnp.cumsum(loads) - loads)[mine]
    rows = order.shape[0] + held * tile     # every expert pads under a tile
    slot = jnp.where(key[order] < held, (ends - room)[mine] + rank, rows)
    slots = jnp.full((rows,), order.shape[0], jnp.int32).at[slot].set(
        order, mode="drop")
    return slots, ends, loads


def _walk_tiled(x, slots, ends, k, chunk):
    """`_walk` over `_plan_tiled`'s rows: a row is live where it holds a
    pair, and a chunk's group sizes are whole tiles."""
    none = x.shape[0] * k
    slots = jnp.pad(slots, (0, -slots.shape[0] % chunk + chunk),
                    constant_values=none)
    before = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])

    def rows(c):
        start = c * chunk
        pairs = jax.lax.dynamic_slice(slots, (start,), (chunk,))
        live = pairs < none
        pairs = jnp.where(live, pairs, 0)
        sizes = (jnp.clip(ends - start, 0, chunk)
                 - jnp.clip(before - start, 0, chunk)).astype(jnp.int32)
        return pairs, pairs // k, live, sizes

    return (ends[-1] + chunk - 1) // chunk, rows


def _widened(wu, wd):
    """The two stacked leaves with the experts' width padded with zeros to
    whole tiles of `TILE` columns (relu(0)^2 = 0 adds nothing, and takes no
    gradient): at width 1856 a grouped product took 1.27-1.55 ms where at
    2048 it takes 0.48-0.61 (my chip run, PR 34)."""
    extra = -wu.shape[1] % TILE if wu.shape[1] > TILE else 0
    if not extra:
        return wu, wd
    return (jnp.pad(wu, ((0, 0), (0, extra), (0, 0))),
            jnp.pad(wd, ((0, 0), (0, 0), (0, extra))))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _experts_relu2(x, w, slots, ends, wu, wd, k, chunk):
    """The ungated form: relu(x W_up^T)^2 W_down^T a pair."""
    trips, rows = _walk_tiled(x, slots, ends, k, chunk)
    w_flat = w.reshape(-1)
    wu_t, wd_t = _turned(*_widened(wu, wd))

    def trip(c, out):
        pairs, tok, live, sizes = rows(c)
        a, = _up(x[tok], sizes, live, wu_t)
        h = jnp.square(jnp.maximum(a, 0.0)).astype(x.dtype)
        y = _grouped(h, wd_t, sizes, "nn") * w_flat[pairs][:, None]
        return out.at[tok].add(jnp.where(live[:, None], y, 0.0))

    out = jax.lax.fori_loop(0, trips, trip, jnp.zeros(x.shape, _F32))
    return out.astype(x.dtype)


def _experts_relu2_fwd(x, w, slots, ends, wu, wd, k, chunk):
    return (_experts_relu2(x, w, slots, ends, wu, wd, k, chunk),
            (x, w, slots, ends, wu, wd))


def _experts_relu2_bwd(k, chunk, res, g):
    x, w, slots, ends, wu, wd = res
    with telemetry.span("moe.build", category="compile",
                        tags={"pass": "bwd"}):
        trips, rows = _walk_tiled(x, slots, ends, k, chunk)
        w_flat = w.reshape(-1)
        wide_u, wide_d = _widened(wu, wd)
        wu_t, wd_t = _turned(wide_u, wide_d)

        def trip(c, carry):
            dx, dw, dwu, dwd = carry
            pairs, tok, live, sizes = rows(c)
            xs = x[tok]
            a, = _up(xs, sizes, live, wu_t)
            act = jnp.maximum(a, 0.0)
            h = jnp.square(act).astype(x.dtype)
            gy = jnp.where(live[:, None], g[tok].astype(_F32), 0.0)
            y = _grouped(h, wd_t, sizes, "nn")
            dw = dw.at[pairs].add(
                jnp.where(live, jnp.sum(gy * y, axis=-1), 0.0))
            dy = (gy * w_flat[pairs][:, None]).astype(x.dtype)
            dh = jnp.where(live[:, None],
                           _grouped(dy, wide_d, sizes, "nn"), 0.0)
            dwd = dwd + _grouped(dy, h, sizes, "tn")
            da = (dh * 2.0 * act).astype(x.dtype)
            dwu = dwu + _grouped(da, xs, sizes, "tn")
            dxs = _grouped(da, wide_u, sizes, "nn")
            dx = dx.at[tok].add(jnp.where(live[:, None], dxs, 0.0))
            return dx, dw, dwu, dwd

        dx, dw, dwu, dwd = jax.lax.fori_loop(
            0, trips, trip,
            (jnp.zeros(x.shape, _F32), jnp.zeros(w_flat.shape, _F32),
             jnp.zeros(wide_u.shape, _F32), jnp.zeros(wide_d.shape, _F32)))
    zero = functools.partial(np.zeros, dtype=jax.dtypes.float0)
    f = wu.shape[1]
    return (dx.astype(x.dtype), dw.reshape(w.shape).astype(w.dtype),
            zero(slots.shape), zero(ends.shape),
            dwu[:, :f].astype(wu.dtype), dwd[:, :, :f].astype(wd.dtype))


_experts_relu2.defvjp(_experts_relu2_fwd, _experts_relu2_bwd)


def moe_experts(x, topk_weight, topk_index, gate_weight, up_weight,
                down_weight, num_experts, expert_offset=0):
    """(out, loads): the share of the expert layer's result that experts
    `expert_offset` .. + held - 1 of `num_experts` give for x (.., d), and
    the pairs each of them received, (held,) int32. `gate_weight` None: the
    ungated squared-ReLU experts."""
    global _tokens_last_traced
    held, d = up_weight.shape[0], x.shape[-1]
    if expert_offset < 0 or expert_offset + held > num_experts:
        raise ValueError("moe_experts: experts %d..%d of %d" % (
            expert_offset, expert_offset + held - 1, num_experts))
    k = topk_index.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    bound = n * min(k, held)
    chunk = min(CHUNK, -(-bound // 128) * 128)
    _tokens_last_traced = n
    telemetry.gauge("moe_experts_held", help="experts the last expert "
                    "layer traced holds").set(held)
    telemetry.gauge("moe_dispatch_rows_bound", help="(token, expert) pairs "
                    "the last expert layer traced can be handed at most: "
                    "tokens x min(top_k, held); the dispatch loop walks the "
                    "pairs it is handed").set(bound)
    with telemetry.span("moe.build", category="compile",
                        tags={"pass": "fwd", "tokens": n, "held": held,
                              "chunk": chunk}):
        w = topk_weight.reshape(n, k).astype(_F32)
        if gate_weight is None:
            slots, ends, loads = _plan_tiled(
                topk_index, held, int(expert_offset),
                TILE if chunk % TILE == 0 else 128)
            out = _experts_relu2(xf, w, slots, ends, up_weight, down_weight,
                                 k, chunk)
        else:
            order, ends, loads = _plan(topk_index, held, int(expert_offset))
            out = _experts(xf, w, order, ends, gate_weight, up_weight,
                           down_weight, k, chunk)
    return out.reshape(x.shape), loads


def observe_loads(loads):
    """Counts what `fit` fetched at a metric sync: `loads` is one (held,)
    array an expert layer, of one step."""
    loads = [np.asarray(v, np.float64) for v in loads]
    telemetry.counter("moe_pairs_routed", help="(token, expert) pairs the "
                      "expert layers were handed, over the steps whose "
                      "loads fit fetched (one a metric sync)"
                      ).inc(int(sum(v.sum() for v in loads)))
    telemetry.counter("moe_tokens_seen", help="tokens the expert layers "
                      "took, a layer a count, over the same steps"
                      ).inc(_tokens_last_traced * len(loads))
    worst = max((float(v.max() / v.mean()) for v in loads if v.sum()),
                default=0.0)
    telemetry.gauge("moe_load_max_over_mean", help="the fullest held "
                    "expert's pairs over the mean expert's, the worst "
                    "layer of the last step fetched").set(worst)


def _gated(a):
    if a.get("activation", "silu_gated") not in ("silu_gated", "relu2"):
        raise ValueError("_contrib_MoEExperts: unknown activation %r"
                         % (a.get("activation"),))
    return a.get("activation", "silu_gated") == "silu_gated"


def _experts_op(a, x, topk_weight, topk_index, *weights):
    gate = weights[0] if _gated(a) else None
    return moe_experts(x, topk_weight, topk_index, gate, weights[-2],
                       weights[-1], a.num_experts, a.expert_offset)


def _experts_args(a, shapes):
    data = shapes[0]
    if not data:
        return shapes
    d, f, held = data[-1], a.hidden, a.experts_held
    up = [(held, f, d)] * (2 if _gated(a) else 1)
    return [data, shapes[1], shapes[2]] + up + [(held, d, f)]


def _fetched(heads):
    """`heads`: [(attrs, output index, host value)] of this op's outputs
    among a Module's heads, as fit fetched them."""
    observe_loads([v for _, i, v in heads if i == 1])


register("_contrib_MoEExperts", _experts_op,
         arg_names=lambda a: ["data", "topk_weight", "topk_index"]
         + (["gate_weight"] if _gated(a) else [])
         + ["up_weight", "down_weight"],
         attrs={"num_experts": Required(int), "experts_held": Required(int),
                "hidden": Required(int), "expert_offset": 0,
                "activation": "silu_gated"},
         num_outputs=2, infer_args=_experts_args, on_fetch=_fetched,
         aliases=("moe_experts",))
