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
through the same walk and grouped products.

Dispatch is dropless by construction. One plan serves both forms
(`_plan_tiled`): the (token, slot) pairs are sorted by expert (a stable
argsort of the local expert index, pairs routed elsewhere last) and every
held expert's pairs are given rows of their own in whole tiles, because the
grouped product's time follows the tiles it visits and not the rows that
hold a pair; the tile follows the shape (512 rows where a trip's rows divide
by it, else 128), and a width over one tile that is not whole tiles is
padded with zeros inside the operator (`_widened`). The walk (`_walk_tiled`)
takes the rows `chunk` at a time for as many trips as the pairs held here
need: gather the rows' tokens, a grouped product over the chunk's ragged
groups (`jax.lax.ragged_dot_general`, which XLA lowers to a Mosaic grouped
matmul on the TPU) for gate, up and down, and the combine. Nothing has a
capacity, so nothing overflows; the static bound is the pairs' array itself
(tokens x top_k int32, and a tile an expert) and one chunk of activations,
and **time follows the pairs routed here**, not tokens x experts held.

On the TPU the combine is owner-computes (`_combined`), the Pallas kernel
`mxtpu_moe_combine` where the shape takes it (`_combine_blocks`): each
token's row is written once, the float32 sum of the trip's rows that hold
its pairs, each read once, times the pair's weight in the forward, in the
data's dtype; no row is scattered. The plan says where each block of
tokens' pairs lie (`_bounds`): an expert's pairs lie in its tiles in token
order, so a block's run of rows in an expert's tiles starts at the
expert's first row plus its pairs among the blocks before. Elsewhere, and
on the CPU, the combine is the scatter-add of the trip's rows into the
tokens' rows. With one trip, the common case, the first trip's combine
writes the result; behind it each trip's combine adds into a float32 sum
the loop carries.

The trip count depends on the data, so the layer brings its own gradient
(``jax.custom_vjp``, one pair for both forms, the activation a function it
is told): the same walk again, each thing once a trip, and the same combine
for dx. Gate and up are recomputed (the up product alone in the ungated form)
and the activation's own derivative is taken from them; the down product is
not: the router weight's gradient comes from the product that carries the
cotangent to the experts' width. Forward and backward take their first trip
as straight-line code (`_combined`): the backward's grouped products over the
ragged contraction ARE the float32 weight gradients, and a loop behind the
first trip adds to them only where the pairs need a second one, which
`moe_trips_after_first` over `moe_layer_steps_seen` counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from .heads import _lowered, _vmem
from .registry import Required, register

COMBINE_KERNEL_NAME = "mxtpu_moe_combine"

_F32 = jnp.float32
CHUNK = 4096        # rows a trip of the dispatch loop, at most
TILE = 512          # rows and columns a tile of XLA's grouped matmul (TPU)
_COMBINE_TOKENS = 256               # tokens a grid step of the combine
_COMBINE_RESIDENT = 64 << 20        # a trip's float32 rows, in VMEM at most

# (tokens, tile, rows a trip) of the last expert layer traced: what the
# fetched loads are counted against (`observe_loads`)
_last_traced = (0, TILE, CHUNK)


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


def _plan_tiled(index, held, offset, tile):
    """Sorts the (token, slot) pairs by the expert held here that they name
    and gives every held expert rows of its own in whole tiles: (order (N k,)
    pair ids, stably sorted, pairs routed elsewhere last; ends (held,), the
    running sum of the loads rounded up to whole tiles of `tile` rows; loads
    (held,)). Which pair a row holds is `_slots`. XLA's grouped matmul on
    the TPU walks the rows in tiles of 512 and takes a tile once for every
    group that has rows in it (0.16 ms a visit at the Nemotron cell's sizes,
    my chip run, PR 34): with the groups packed end to end the visits, and
    so the time, follow where the boundaries happen to fall; with each group
    on tiles of its own they are the experts' own tiles and no more. Six
    seeds of that cell spread by 0.46% packed end to end at the padded width
    and by 0.17% on tiles of their own, at the same median step (my chip
    runs, PR 34). The loads are counted by comparison and the rows' pairs
    found by gathers a trip: a histogram by scatter-add took 0.72 ms a layer
    at Laguna's 81,920 pairs and every row's pair up front 0.61-0.96 ms,
    more than the forward's three products together (my chip runs, PR 35)."""
    local = index.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    loads = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype),
                    axis=0, dtype=jnp.int32)
    return order, jnp.cumsum(-(-loads // tile) * tile), loads


def _slots(order, ends, loads, row):
    """The pair id each of the plan's rows `row` holds, `order.shape[0]`
    where it holds none: by row, the expert whose tiles it lies in, its rank
    among that expert's pairs, and the pair of that rank in the sorted
    order."""
    held = loads.shape[0]
    before = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    mine = jnp.minimum(jnp.sum(row[:, None] >= ends, axis=1), held - 1)
    rank = row - before[mine]
    pair = order[jnp.minimum((jnp.cumsum(loads) - loads)[mine] + rank,
                             order.shape[0] - 1)]
    return jnp.where(rank < loads[mine], pair, order.shape[0])


def _walk_tiled(order, ends, loads, k, chunk):
    """What every trip of the dispatch starts from: `trips`, and `rows(c)`
    -> (pair ids, their tokens, which rows hold a pair, the chunk's group
    sizes, whole tiles) over the plan's rows."""
    edges = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])

    def rows(c):
        start = c * chunk
        pairs = _slots(order, ends, loads,
                       start + jnp.arange(chunk, dtype=jnp.int32))
        live = pairs < order.shape[0]
        pairs = jnp.where(live, pairs, 0)
        inside = jnp.clip(edges - start, 0, chunk)
        sizes = (inside[1:] - inside[:-1]).astype(jnp.int32)
        return pairs, pairs // k, live, sizes

    return (ends[-1] + chunk - 1) // chunk, rows


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


def _widened(leaves):
    """The stacked leaves (gate and up, or up alone; down last) with the
    experts' width padded with zeros to whole tiles of `TILE` columns (both
    activations give 0 there, which adds nothing and takes no gradient): at
    width 1856 a grouped product took 1.27-1.55 ms where at 2048 it takes
    0.48-0.61 (my chip run, PR 34). A width of whole tiles, or under one, is
    left as it is."""
    f = leaves[-1].shape[2]
    extra = -f % TILE if f > TILE else 0
    if not extra:
        return leaves
    return (tuple(jnp.pad(u, ((0, 0), (0, extra), (0, 0)))
                  for u in leaves[:-1])
            + (jnp.pad(leaves[-1], ((0, 0), (0, 0), (0, extra))),))


def _silu_gated(a, b):
    return jax.nn.silu(a) * b


def _relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def _plus(acc, new):
    """A sum that its first term starts: no zeros to add it to."""
    return new if acc is None else acc + new


# ------------------------------------------------------------- the combine
def _bounds(index, held, offset, ends, tokens):
    """Where the pairs of each block of `tokens` tokens lie among the plan's
    rows: flat ((N / tokens + 1) x held,) int32, at b x held + e the first
    row of held expert e's pairs among block b's tokens and, last, past each
    expert's last pair. An expert's pairs lie in its tiles in token order
    (the plan's sort is stable), so that is its first row plus its pairs
    among the blocks before."""
    k = index.shape[-1]
    local = index.reshape(-1, tokens * k) - offset
    mine = jnp.sum(local[:, :, None] == jnp.arange(held, dtype=local.dtype),
                   axis=1, dtype=jnp.int32)
    before = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    runs = jnp.cumsum(jnp.concatenate(
        [jnp.zeros((1, held), jnp.int32), mine]), axis=0)
    return (before + runs).astype(jnp.int32).reshape(-1)


def _combine_blocks(n, d, chunk):
    """Tokens a grid step of the combine kernel, or None where the shape
    does not take it: tokens a multiple of `_COMBINE_TOKENS`, d of 128
    lanes, and a trip's float32 rows within `_COMBINE_RESIDENT` of VMEM."""
    if n % _COMBINE_TOKENS or d % 128 or chunk * d * 4 > _COMBINE_RESIDENT:
        return None
    return _COMBINE_TOKENS


def _combine_kernel(edges_ref, tok_ref, *refs, held, weighted, summed):
    """A block of tokens: for each held expert the run of the trip's rows
    its pairs among the block's tokens hold (`edges_ref`, scalar-prefetched:
    block b, expert e at b x held + e), each row read once from the trip's
    rows resident in VMEM, times its pair's weight where there are weights,
    and added in float32 to its token's row (`tok_ref`: each row's token)
    of the sum carried so far or of 0; the block written once, in the
    output's dtype."""
    w_ref = refs[0] if weighted else None
    rows_ref, *refs = refs[1:] if weighted else refs
    prior_ref, out_ref, acc_ref = refs if summed else (None,) + tuple(refs)
    b = pl.program_id(0)
    first = b * out_ref.shape[0]
    acc_ref[...] = (prior_ref[...] if summed
                    else jnp.zeros(acc_ref.shape, _F32))

    def row(r, carry):
        y = rows_ref[pl.ds(r, 1), :]
        at = pl.ds(tok_ref[r] - first, 1)
        acc_ref[at, :] += y * w_ref[r] if weighted else y
        return carry

    def expert(e, carry):
        return lax.fori_loop(edges_ref[b * held + e],
                             edges_ref[(b + 1) * held + e], row, carry)

    lax.fori_loop(0, held, expert, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _combine_call(rows, tok, w, edges, prior, n, dtype, tokens,
                  interpret=False):
    """The kernel over blocks of `tokens` of the n tokens: rows (chunk, d)
    float32 resident in VMEM, each row's token (and weight) in SMEM,
    `edges` the runs' ends in the trip's rows, `prior` (n, d) float32
    aliased to the output."""
    d = rows.shape[1]
    blocks = n // tokens
    block = pl.BlockSpec((tokens, d), lambda b, e: (b, 0))
    lists = [tok] + ([] if w is None else [w])
    args = lists + [rows] + ([] if prior is None else [prior])
    resident = (rows.size + tokens * d) * 4 \
        + 2 * tokens * d * (jnp.dtype(dtype).itemsize
                            + (0 if prior is None else 4))
    return pl.pallas_call(
        functools.partial(_combine_kernel,
                          held=edges.shape[0] // (blocks + 1),
                          weighted=w is not None, summed=prior is not None),
        out_shape=jax.ShapeDtypeStruct((n, d), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * len(lists)
            + [pl.BlockSpec(memory_space=pltpu.VMEM)]
            + ([] if prior is None else [block]),
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((tokens, d), _F32)]),
        input_output_aliases={} if prior is None else {len(args): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem(resident)),
        interpret=interpret,
        name=COMBINE_KERNEL_NAME,
    )(edges, *args)


def _combine(got, bounds, base, prior, n, dtype, tokens):
    """sum over each token's pairs among a trip's rows, the plan's rows
    base .. base + chunk - 1, times the pair's weight where there are
    weights, added to `prior` (n, d) float32 or to 0: (n, d) in `dtype`.
    `got`: (the trip's rows (chunk, d) float32, each row's token, each row's
    weight or None, which rows hold a pair). The kernel reads only the rows
    `bounds` says hold a pair; the plain body is the scatter-add of the rows
    into the tokens' rows, a row that holds no pair masked."""
    def kernel(rows, tok, w, live, bounds, prior):
        edges = jnp.clip(bounds - base, 0, rows.shape[0])
        return _combine_call(rows, tok, w, edges, prior, n, dtype, tokens)

    def plain(rows, tok, w, live, bounds, prior):
        y = rows if w is None else rows * w[:, None]
        start = jnp.zeros((n, rows.shape[1]), _F32) if prior is None \
            else prior
        return start.at[tok].add(jnp.where(live[:, None], y, 0.0)).astype(
            dtype)

    return _lowered(tokens, kernel, plain, *got, bounds, prior)


def _combined(trips, trip, start, bounds, chunk, n, dtype, tokens):
    """The dispatch, `trip(c, carry)` -> (what `_combine` reads of trip c,
    carry) for c < trips, and its combine into n tokens' rows. Trip 0 is
    straight-line code: it is always taken (with no pair held every group
    is empty and every row dead, which any trip has to get right for an
    expert with no rows), what it returns is what the loop behind it carries
    on, so a sum may start from its first term (`_plus`), and XLA schedules
    the trip nearly every layer-step stops at with the code around it. With
    one trip one combine writes the result in `dtype`; behind a first trip
    the sum is carried in float32, a combine a trip, and rounded once."""
    first, carry = trip(0, start)

    def alone(first, carry):
        return _combine(first, bounds, 0, None, n, dtype, tokens), carry

    def more(first, carry):
        def step(c, state):
            got, carry = trip(c, state[1])
            return (_combine(got, bounds, c * chunk, state[0], n, _F32,
                             tokens), carry)

        out, carry = lax.fori_loop(1, trips, step, (
            _combine(first, bounds, 0, None, n, _F32, tokens), carry))
        return out.astype(dtype), carry

    return lax.cond(trips > 1, more, alone, first, carry)


def _count_combine(tokens):
    telemetry.counter(
        "moe_combine_builds",
        labels={"path": "plain" if tokens is None else "fused"},
        help="combines of differentiated expert layers traced, forward and "
             "backward, by whether their shape takes the combine kernel on "
             "the chip").inc()


# ----------------------------------------------------------------- the walk
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _experts(x, w, plan, leaves, act, k, chunk):
    """sum over the pairs held here of w E(x): `plan` is `_plan_tiled`'s
    and `_bounds`' (None where the shape does not take the combine kernel),
    `leaves` the stacked leaves an expert's `act` reads x through (gate and
    up, or up alone) and, last, the down leaf that takes its result back."""
    trips, rows = _walk_tiled(*plan[:3], k, chunk)
    w_flat = w.reshape(-1)
    *ups_t, wd_t = _turned(*_widened(leaves))

    def trip(c, carry):
        pairs, tok, live, sizes = rows(c)
        h = act(*_up(x[tok], sizes, live, *ups_t)).astype(x.dtype)
        return (_grouped(h, wd_t, sizes, "nn"), tok, w_flat[pairs],
                live), carry

    out, _ = _combined(trips, trip, None, plan[3], chunk, x.shape[0],
                       x.dtype, _combine_blocks(*x.shape, chunk))
    return out


def _experts_fwd(x, w, plan, leaves, act, k, chunk):
    _count_combine(_combine_blocks(*x.shape, chunk))
    return _experts(x, w, plan, leaves, act, k, chunk), (x, w, plan, leaves)


def _experts_bwd(act, k, chunk, res, g):
    """The same walk again. The first trip's grouped products ARE the
    float32 weight gradients (`_combined`, `_plus`), and the loop behind it
    adds to them only where there is a second trip. The router weight's
    gradient sum(g * (h W_down^T)) is taken as sum((g W_down) * h) from the
    product the backward needs anyway, the pair's weight applied in float32
    after it. dx is the combine of the rows' input gradients."""
    x, w, plan, leaves = res
    tokens = _combine_blocks(*x.shape, chunk)
    _count_combine(tokens)
    with telemetry.span("moe.build", category="compile",
                        tags={"pass": "bwd"}):
        trips, rows = _walk_tiled(*plan[:3], k, chunk)
        w_flat = w.reshape(-1)
        *wide, wide_d = _widened(leaves)
        ups_t = _turned(*wide)

        def trip(c, carry):
            dw, dleaves = carry
            pairs, tok, live, sizes = rows(c)
            xs, gs, wp = x[tok], g[tok], w_flat[pairs][:, None]
            h, pull = jax.vjp(act, *_up(xs, sizes, live, *ups_t))
            dh = jnp.where(live[:, None],
                           _grouped(gs, wide_d, sizes, "nn"), 0.0)
            dw = dw.at[pairs].add(jnp.sum(dh * h, axis=-1))
            das = [d.astype(x.dtype) for d in pull(dh * wp)]
            new = [_grouped(da, xs, sizes, "tn") for da in das] + [
                _grouped(gs, (h * wp).astype(x.dtype), sizes, "tn")]
            dxs = sum(_grouped(da, u, sizes, "nn")
                      for da, u in zip(das, wide))
            return (dxs, tok, None, live), (dw,
                                            tuple(map(_plus, dleaves, new)))

        dx, (dw, dleaves) = _combined(
            trips, trip, (jnp.zeros(w_flat.shape, _F32),
                          (None,) * len(leaves)),
            plan[3], chunk, x.shape[0], x.dtype, tokens)
    f = leaves[-1].shape[2]      # the published width, under the padding
    dleaves = [d[:, :f] for d in dleaves[:-1]] + [dleaves[-1][:, :, :f]]
    return (dx, dw.reshape(w.shape).astype(w.dtype),
            jax.tree.map(lambda v: np.zeros(v.shape, jax.dtypes.float0),
                         plan),
            tuple(d.astype(v.dtype) for d, v in zip(dleaves, leaves)))


_experts.defvjp(_experts_fwd, _experts_bwd)


def moe_experts(x, topk_weight, topk_index, gate_weight, up_weight,
                down_weight, num_experts, expert_offset=0):
    """(out, loads): the share of the expert layer's result that experts
    `expert_offset` .. + held - 1 of `num_experts` give for x (.., d), and
    the pairs each of them received, (held,) int32. `gate_weight` None: the
    ungated squared-ReLU experts."""
    global _last_traced
    held, d = up_weight.shape[0], x.shape[-1]
    if expert_offset < 0 or expert_offset + held > num_experts:
        raise ValueError("moe_experts: experts %d..%d of %d" % (
            expert_offset, expert_offset + held - 1, num_experts))
    k = topk_index.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    bound = n * min(k, held)
    chunk = min(CHUNK, -(-bound // 128) * 128)
    tile = TILE if chunk % TILE == 0 else 128
    _last_traced = (n, tile, chunk)
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
        plan = _plan_tiled(topk_index, held, int(expert_offset), tile)
        tokens = _combine_blocks(n, d, chunk)
        plan += (None if tokens is None else _bounds(
            topk_index, held, int(expert_offset), plan[1], tokens),)
        leaves, act = (((up_weight, down_weight), _relu2)
                       if gate_weight is None else
                       ((gate_weight, up_weight, down_weight), _silu_gated))
        out = _experts(xf, w, plan, leaves, act, k, chunk)
    return out.reshape(x.shape), plan[2]


def observe_loads(loads):
    """Counts what `fit` fetched at a metric sync: `loads` is one (held,)
    array an expert layer, of one step."""
    tokens, tile, chunk = _last_traced
    loads = [np.asarray(v, np.int64) for v in loads]
    telemetry.counter("moe_pairs_routed", help="(token, expert) pairs the "
                      "expert layers were handed, over the steps whose "
                      "loads fit fetched (one a metric sync)"
                      ).inc(int(sum(v.sum() for v in loads)))
    telemetry.counter("moe_tokens_seen", help="tokens the expert layers "
                      "took, a layer a count, over the same steps"
                      ).inc(tokens * len(loads))
    telemetry.counter("moe_layer_steps_seen", help="expert layers whose "
                      "loads fit fetched, a layer of a step a count"
                      ).inc(len(loads))
    telemetry.counter("moe_trips_after_first", help="trips of the dispatch "
                      "those layers took behind the first, which runs "
                      "outside the loop: the loads in whole tiles over the "
                      "rows of a trip, less one").inc(sum(
                          max(-(-rows // chunk), 1) - 1 for rows in (
                              int((-(-v // tile)).sum()) * tile
                              for v in loads)))
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
