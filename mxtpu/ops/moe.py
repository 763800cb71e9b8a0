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
received, (held,) int32, which takes no gradient.

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
same walk again, recomputing gate and up, with the weight gradients
accumulated in float32 by grouped products whose ragged dimension is the
contraction.
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
    local = index.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    loads = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    return order, jnp.cumsum(loads), loads


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


def _gate_up(xs, sizes, live, wg_t, wu_t):
    """x W_gate^T and x W_up^T of a chunk's rows, 0 in rows that hold no
    pair: selects, not products, for rows past the last pair are in no group
    and hold whatever the grouped product left there."""
    return tuple(jnp.where(live[:, None], _grouped(xs, t, sizes, "nn"), 0.0)
                 for t in (wg_t, wu_t))


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
        a, b = _gate_up(x[tok], sizes, live, wg_t, wu_t)
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
            a, b = _gate_up(xs, sizes, live, wg_t, wu_t)
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


def moe_experts(x, topk_weight, topk_index, gate_weight, up_weight,
                down_weight, num_experts, expert_offset=0):
    """(out, loads): the share of the expert layer's result that experts
    `expert_offset` .. + held - 1 of `num_experts` give for x (.., d), and
    the pairs each of them received, (held,) int32. `chunk`: rows a trip
    of the dispatch loop (0: from the shape, at most `CHUNK`)."""
    global _tokens_last_traced
    held, d = gate_weight.shape[0], x.shape[-1]
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
        order, ends, loads = _plan(topk_index, held, int(expert_offset))
        out = _experts(xf, topk_weight.reshape(n, k).astype(_F32), order,
                       ends, gate_weight, up_weight, down_weight, k, chunk)
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


def _experts_op(a, x, topk_weight, topk_index, gate_weight, up_weight,
                down_weight):
    return moe_experts(x, topk_weight, topk_index, gate_weight, up_weight,
                       down_weight, a.num_experts, a.expert_offset)


def _experts_args(a, shapes):
    data = shapes[0]
    if not data:
        return shapes
    d, f, held = data[-1], a.hidden, a.experts_held
    return [data, shapes[1], shapes[2], (held, f, d), (held, f, d),
            (held, d, f)]


def _fetched(heads):
    """`heads`: [(attrs, output index, host value)] of this op's outputs
    among a Module's heads, as fit fetched them."""
    observe_loads([v for _, i, v in heads if i == 1])


register("_contrib_MoEExperts", _experts_op,
         arg_names=["data", "topk_weight", "topk_index", "gate_weight",
                    "up_weight", "down_weight"],
         attrs={"num_experts": Required(int), "experts_held": Required(int),
                "hidden": Required(int), "expert_offset": 0},
         num_outputs=2, infer_args=_experts_args, on_fetch=_fetched,
         aliases=("moe_experts",))
