"""The gated delta rule (Gated DeltaNet: Yang, Kautz, Hatamizadeh,
arXiv:2412.06464; negative eigenvalues: Grazzi et al., arXiv:2411.12537) as
chunked Pallas TPU kernels, forward and backward.

Per head, with a state S in R^{d_k x d_v}, S_0 = 0, and per token t a query
q_t, key k_t, value v_t, log-decay g_t <= 0 and write strength beta_t:

    S~ = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S~^T k_t)
    S_t = S~ + k_t u_t^T;   o_t = S_t^T q_t

The kernels never step a token at a time. A row of T tokens is cut into
chunks of C (64); inside a chunk, with gamma the running sum of g from the
chunk's start, M_tj = exp(gamma_t - gamma_j) and the chunk's first state S
(the WY form of arXiv:2406.06484 with the decay folded in as in
arXiv:2412.06464 section 3):

    A = diag(beta) (M_{j<t} * K K^T)           strictly lower, C x C
    U = (I + A)^{-1} diag(beta) (V - diag(e^gamma) K S)
    O = diag(e^gamma) Q S + (M_{j<=t} * Q K^T) U
    S' = e^{gamma_C} S + (diag(e^{gamma_C - gamma}) K)^T U

so a chunk is matrix products and one unit-lower-triangular solve, and only
the T/C states between chunks are stepped through. (I + A)^{-1} is taken by
block forward substitution (`_inverse`), in float32 at `highest`; gamma, M
and the state are float32
whatever the operands' dtype, and every exponent taken is <= 0. Matrix
operands are cast to the dtype of q for the MXU, as the flash kernels do.

Forward: grid (batch*heads, T / (C * chunks a step)); the state is carried
in VMEM scratch along the second axis. Under differentiation two residuals
are written out besides the inputs: each chunk's first state, float32,
(B*H, T/C, d_k, d_v) numbers, and each chunk's T = (I + A)^{-1} in the dtype
of q, C x C numbers a chunk, those of a grid step side by side (whole
128-lane tiles in HBM). Backward (``jax.custom_vjp``): the same grid walked
from the last chunk to the first with dS carried in scratch; each chunk is
recomputed from its saved first state and its saved T and differentiated by
hand (`_chunk_bwd`). Handing T over is exact: T depends on k, g and beta
alone, the backward differentiates through U = T X by dX = T^T dU and
dA = -dX U^T and never through the inverse's own products, and it reads T
only in the dtype the forward had already cast it to for its own product.
So `_inverse`, four fifths of a forward chunk's instructions, runs once a
chunk a step, not twice. No per-token state reaches HBM in either direction.

Which program runs follows the platform the program is lowered for, as in
ops/attention.py: the Mosaic kernels on ``tpu``; on ``cpu`` the same chunk
functions under ``lax.scan`` and ``vmap`` (the kernels themselves run on
the CPU only in tests, in interpret mode, against that path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from .registry import register

CHUNK = 64
FWD_KERNEL_NAME = "mxtpu_delta_rule_fwd"
BWD_KERNEL_NAME = "mxtpu_delta_rule_bwd"

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_AB = (((1,), (0,)), ((), ()))    # a @ b
_ABT = (((1,), (1,)), ((), ()))   # a @ b.T
_ATB = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims=_AB, precision=None):
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=_F32)


def _masks(c):
    """(j <= t, j < t, j == t) over (t, j) in C x C."""
    t = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return j <= t, j < t, j == t


def _col(row, eye):
    """(1, C) -> (C, 1) without a transpose: the diagonal of the row
    broadcast down, summed along the lanes."""
    c = row.shape[1]
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (c, c)), 0.0),
                   axis=1, keepdims=True)


def _row(col, eye):
    c = col.shape[0]
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(col, (c, c)), 0.0),
                   axis=0, keepdims=True)


def _inverse(a):
    """(I + A)^{-1} of a strictly lower-triangular A (C x C, float32) by
    block forward substitution on whole matrices: with T the inverse of the
    diagonal blocks of size b, the inverse of those of size 2b is
    T - T A_off T, A_off being A's entries inside a 2b-block and outside
    its two b-blocks ([[L11, 0], [L21, L22]]^{-1} has -T22 L21 T11 below).
    2 (log2(C) - 1) products at `highest`. The Neumann product
    (I - A)(I + A^2)(I + A^4)... costs the same and loses everything to
    cancellation once A's powers grow (beta near 2, keys far from
    orthogonal)."""
    c = a.shape[0]
    t = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def off(shift):
        inside = jnp.right_shift(t, shift + 1) == jnp.right_shift(j, shift + 1)
        apart = jnp.right_shift(t, shift) != jnp.right_shift(j, shift)
        return jnp.where(inside & apart, a, 0.0)

    inv = jnp.where(t == j, 1.0, 0.0) - off(0)
    shift = 1
    while 2 << shift <= c:
        inv = inv - _dot(_dot(inv, off(shift), precision=_HI), inv,
                         precision=_HI)
        shift += 1
    return inv


def _chunk_prep(q, k, g_row, b_row):
    """What a chunk needs that depends neither on its first state nor on
    (I + A)^{-1}: the decays, A and the masked, decayed Q K^T. Rows (1, C)
    in, columns (C, 1) out."""
    c = k.shape[0]
    incl, strict, eye = _masks(c)
    g_row = g_row.astype(_F32)
    g_col = _col(g_row, eye)
    b_col = _col(b_row.astype(_F32), eye)
    # gamma_t = sum_{i<=t} g_i, as a column and as a row
    gam_col = jnp.sum(jnp.where(incl, jnp.broadcast_to(g_row, (c, c)), 0.0),
                      axis=1, keepdims=True)
    gam_row = jnp.sum(jnp.where(strict, 0.0, jnp.broadcast_to(g_col, (c, c))),
                      axis=0, keepdims=True)
    total = jnp.sum(g_row, axis=1, keepdims=True)            # gamma_C, (1, 1)
    # exponents are masked before exp: above the diagonal they are > 0
    m_full = jnp.where(incl, jnp.exp(jnp.where(incl, gam_col - gam_row, 0.0)),
                       0.0)
    kk = _dot(k, k, _ABT)
    msp = jnp.where(strict, m_full * kk, 0.0)
    att = m_full * _dot(q, k, _ABT)
    return {"b": b_col, "eg": jnp.exp(gam_col), "ed": jnp.exp(total - gam_col),
            "a_end": jnp.exp(total), "m": m_full, "msp": msp,
            "A": b_col * msp, "att": att}


def _chunk_state(p, t_inv, s, q, k, v):
    """The part that needs the chunk's first state `s` (float32) and
    `t_inv`, (I + A)^{-1} in the dtype of q: U, the chunk's output and its
    last state."""
    cd = q.dtype
    s_c = s.astype(cd)
    ks = _dot(k, s_c)
    y = v.astype(_F32) - p["eg"] * ks
    x = p["b"] * y
    u = _dot(t_inv, x.astype(cd))
    qs = _dot(q, s_c)
    o = p["eg"] * qs + _dot(p["att"].astype(cd), u.astype(cd))
    edk = (p["ed"] * k.astype(_F32)).astype(cd)
    s_next = p["a_end"] * s + _dot(edk, u.astype(cd), _ATB)
    return o, s_next, {"ks": ks, "y": y, "x": x, "u": u, "qs": qs, "edk": edk}


def _chunk_fwd(s, q, k, v, g_row, b_row):
    """(o, the chunk's last state, (I + A)^{-1} as the products use it)."""
    p = _chunk_prep(q, k, g_row, b_row)
    t_inv = _inverse(p["A"]).astype(q.dtype)
    o, s_next, _ = _chunk_state(p, t_inv, s, q, k, v)
    return o, s_next, t_inv


def _chunk_bwd(s, t_inv, q, k, v, g_row, b_row, do, ds_next):
    """Gradients of one chunk, recomputed from its first state `s` and the
    forward's `t_inv`: (dq, dk, dv, dg (1, C), dbeta (1, C), ds). `do` is
    the gradient of the chunk's output, `ds_next` of its last state
    (float32)."""
    cd = q.dtype
    c = k.shape[0]
    incl, strict, eye = _masks(c)
    p = _chunk_prep(q, k, g_row, b_row)
    _, _, r = _chunk_state(p, t_inv, s, q, k, v)
    s_c = s.astype(cd)
    u_c = r["u"].astype(cd)
    do32 = do.astype(_F32)
    kf = k.astype(_F32)
    dsn_c = ds_next.astype(cd)

    # O = eg * (Q S) + att U;  S' = a_end S + (ed K)^T U
    du = _dot(p["att"].astype(cd), do, _ATB) + _dot(r["edk"], dsn_c)
    datt = _dot(do, u_c, _ABT)
    ego = (p["eg"] * do32).astype(cd)
    dq = _dot(ego, s_c, _ABT)
    ds = p["a_end"] * ds_next + _dot(q, ego, _ATB)
    deg = jnp.sum(do32 * r["qs"], axis=1, keepdims=True)
    da_end = jnp.sum(ds_next * s, keepdims=True)
    dedk = _dot(u_c, dsn_c, _ABT)
    dk = p["ed"] * dedk
    ded = jnp.sum(dedk * kf, axis=1, keepdims=True)

    # U = T X with T = (I + A)^{-1}: dX = T^T dU, dA = -dX U^T
    dx = _dot(t_inv, du.astype(cd), _ATB)
    da = jnp.where(strict, -_dot(dx.astype(cd), u_c, _ABT), 0.0)

    # X = beta * Y, Y = V - eg * (K S)
    dbeta = jnp.sum(dx * r["y"], axis=1, keepdims=True)
    dy = p["b"] * dx
    dv = dy
    egy = (p["eg"] * dy).astype(cd)
    dk = dk - _dot(egy, s_c, _ABT)
    ds = ds - _dot(k, egy, _ATB)
    deg = deg - jnp.sum(dy * r["ks"], axis=1, keepdims=True)

    # A = beta * (M_strict * K K^T)
    dbeta = dbeta + jnp.sum(da * p["msp"], axis=1, keepdims=True)
    dkk = (p["b"] * p["m"] * da).astype(cd)
    dk = dk + _dot(dkk, k) + _dot(dkk, k, _ATB)
    dd = da * p["A"]

    # att = M * Q K^T
    dqk = (p["m"] * datt).astype(cd)
    dq = dq + _dot(dqk, k)
    dk = dk + _dot(dqk, q, _ATB)
    dd = dd + datt * p["att"]

    # D_tj = gamma_t - gamma_j; eg = e^gamma; ed = e^{gamma_C - gamma};
    # a_end = e^{gamma_C}; gamma = cumsum(g)
    ded_e = ded * p["ed"]
    dgam_col = (jnp.sum(dd, axis=1, keepdims=True)
                - _col(jnp.sum(dd, axis=0, keepdims=True), eye)
                + deg * p["eg"] - ded_e)
    dtotal = jnp.sum(ded_e, keepdims=True) + da_end * p["a_end"]
    # dg_t = sum_{i>=t} dgamma_i, straight into a row
    dg = jnp.sum(jnp.where(incl, jnp.broadcast_to(dgam_col, (c, c)), 0.0),
                 axis=0, keepdims=True) + dtotal
    return dq, dk, dv, dg, _row(dbeta, eye), ds


# ------------------------------------------------------------------ kernels
def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, *refs, chunk, per_step):
    """`per_step` chunks of one head: outputs o and, under differentiation,
    each chunk's first state and its (I + A)^{-1}; the state is carried in
    the scratch."""
    o_ref, s_ref = refs[0], refs[-1]
    s_out, t_out = refs[1:-1] or (None, None)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    # what does not depend on the state first, for every chunk of the step:
    # independent work the scheduler can overlap with the serial part
    rows = [pl.ds(i * chunk, chunk) for i in range(per_step)]
    preps = [_chunk_prep(q_ref[0, r, :], k_ref[0, r, :], g_ref[0, i],
                         b_ref[0, i]) for i, r in enumerate(rows)]
    t_invs = [_inverse(p["A"]).astype(q_ref.dtype) for p in preps]
    s = s_ref[...]
    for i, r in enumerate(rows):
        if s_out is not None:
            s_out[0, i] = s
            t_out[0, 0, :, r] = t_invs[i]    # chunk i's C lanes of the step's
        o, s, _ = _chunk_state(preps[i], t_invs[i], s, q_ref[0, r, :],
                               k_ref[0, r, :], v_ref[0, r, :])
        o_ref[0, r, :] = o.astype(o_ref.dtype)
    s_ref[...] = s


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_in, t_in, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *, chunk,
                per_step):
    """The same chunks, last to first, with dS carried in the scratch."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    ds = ds_ref[...]
    for i in reversed(range(per_step)):
        r = pl.ds(i * chunk, chunk)
        dq, dk, dv, dg, db, ds = _chunk_bwd(
            s_in[0, i], t_in[0, 0, :, r], q_ref[0, r, :], k_ref[0, r, :],
            v_ref[0, r, :], g_ref[0, i], b_ref[0, i], do_ref[0, r, :], ds)
        dq_ref[0, r, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, r, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, r, :] = dv.astype(dv_ref.dtype)
        dg_ref[0, i] = dg
        db_ref[0, i] = db
    ds_ref[...] = ds


def _per_step(n_chunks):
    """Chunks a grid step: the most of 4, 2, 1 that divides the row (1, 2,
    4 and 8 read 16.0 to 17.4 ms for one layer's forward and backward at the
    hybrid cell's size: PERF.md, PR 28)."""
    return next(p for p in (4, 2, 1) if n_chunks % p == 0)


def _specs(bh, t, dk, dv, chunk, per_step, reverse):
    """Block specs of (q or k, v, a row of g or beta, the states, the
    inverses)."""
    steps = t // (chunk * per_step)
    at = (lambda n: steps - 1 - n) if reverse else (lambda n: n)
    qk = pl.BlockSpec((1, chunk * per_step, dk), lambda b, n: (b, at(n), 0))
    vv = pl.BlockSpec((1, chunk * per_step, dv), lambda b, n: (b, at(n), 0))
    # rows of g and beta as (1, C) tiles: their last two dims are the
    # array's own, so any C is a legal block
    row = pl.BlockSpec((1, per_step, 1, chunk), lambda b, n: (b, at(n), 0, 0))
    st = pl.BlockSpec((1, per_step, dk, dv), lambda b, n: (b, at(n), 0, 0))
    # a grid step's inverses side by side, (C, per_step * C): whole
    # 128-lane tiles in HBM where a (C, C) array of its own is padded to them
    inv = pl.BlockSpec((1, 1, chunk, chunk * per_step),
                       lambda b, n: (b, at(n), 0, 0))
    return steps, qk, vv, row, st, inv


def _fwd_call(q, k, v, g, beta, chunk, with_states, interpret):
    bh, t, dk = q.shape
    dv = v.shape[2]
    n = t // chunk
    per_step = _per_step(n)
    steps, qk, vv, row, st, inv = _specs(bh, t, dk, dv, chunk, per_step,
                                         False)
    out_shape = [jax.ShapeDtypeStruct((bh, t, dv), v.dtype)]
    out_specs = [vv]
    if with_states:
        out_shape += [jax.ShapeDtypeStruct((bh, n, dk, dv), _F32),
                      jax.ShapeDtypeStruct(
                          (bh, steps, chunk, chunk * per_step), q.dtype)]
        out_specs += [st, inv]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, per_step=per_step),
        out_shape=out_shape, grid=(bh, steps),
        in_specs=[qk, qk, vv, row, row], out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=FWD_KERNEL_NAME,
    )(q, k, v, g.reshape(bh, n, 1, chunk), beta.reshape(bh, n, 1, chunk))
    return tuple(out) if with_states else out[0]


def _bwd_call(q, k, v, g, beta, states, t_invs, do, chunk, interpret):
    bh, t, dk = q.shape
    dv = v.shape[2]
    n = t // chunk
    per_step = _per_step(n)
    steps, qk, vv, row, st, inv = _specs(bh, t, dk, dv, chunk, per_step, True)
    rows = jax.ShapeDtypeStruct((bh, n, 1, chunk), _F32)
    dq, dk_, dv_, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, per_step=per_step),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), rows, rows],
        grid=(bh, steps),
        in_specs=[qk, qk, vv, row, row, st, inv, vv],
        out_specs=[qk, qk, vv, row, row],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=BWD_KERNEL_NAME,
    )(q, k, v, g.reshape(bh, n, 1, chunk), beta.reshape(bh, n, 1, chunk),
      states, t_invs, do)
    return dq, dk_, dv_, dg.reshape(bh, t), db.reshape(bh, t)


# ------------------------------------------- the same chunks without Mosaic
def _chunked(x, chunk):
    """(BH, T, ...) -> (T / C, BH, C, ...): chunks first, for `lax.scan`."""
    bh, t = x.shape[:2]
    return jnp.moveaxis(x.reshape((bh, t // chunk, chunk) + x.shape[2:]), 1, 0)


def _unchunked(x):
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


def _side_by_side(t_invs, per_step):
    """(BH, T / C, C, C) -> (BH, steps, C, per_step * C), the kernels'
    layout of the inverses."""
    bh, n, c, _ = t_invs.shape
    t_invs = t_invs.reshape(bh, n // per_step, per_step, c, c)
    return jnp.swapaxes(t_invs, 2, 3).reshape(bh, n // per_step, c,
                                              per_step * c)


def _one_by_one(t_invs, chunk):
    """`_side_by_side` undone: (BH, T / C, C, C)."""
    bh, steps, _, wide = t_invs.shape
    t_invs = t_invs.reshape(bh, steps, chunk, wide // chunk, chunk)
    return jnp.swapaxes(t_invs, 2, 3).reshape(bh, -1, chunk, chunk)


def _scan_fwd(q, k, v, g, beta, chunk, with_states):
    bh, _, dk = q.shape
    body = jax.vmap(_chunk_fwd)

    def step(s, xs):
        qc, kc, vc, gc, bc = xs
        o, s_next, t_inv = body(s, qc, kc, vc, gc[:, None, :],
                                bc[:, None, :])
        return s_next, (o.astype(v.dtype), s, t_inv)

    xs = tuple(_chunked(x, chunk) for x in (q, k, v, g, beta))
    _, (o, states, t_invs) = lax.scan(
        step, jnp.zeros((bh, dk, v.shape[2]), _F32), xs)
    o = _unchunked(o)
    if not with_states:
        return o
    return o, jnp.moveaxis(states, 0, 1), _side_by_side(
        jnp.moveaxis(t_invs, 0, 1), _per_step(t_invs.shape[0]))


def _scan_bwd(q, k, v, g, beta, states, t_invs, do, chunk):
    bh, _, dk = q.shape
    body = jax.vmap(_chunk_bwd)

    def step(ds, xs):
        qc, kc, vc, gc, bc, sc, tc, doc = xs
        dq, dk_, dv_, dg, db, ds = body(sc, tc, qc, kc, vc, gc[:, None, :],
                                        bc[:, None, :], doc, ds)
        return ds, (dq.astype(q.dtype), dk_.astype(k.dtype),
                    dv_.astype(v.dtype), dg[:, 0], db[:, 0])

    xs = tuple(_chunked(x, chunk) for x in (q, k, v, g, beta)) \
        + (jnp.moveaxis(states, 1, 0),
           jnp.moveaxis(_one_by_one(t_invs, chunk), 1, 0),
           _chunked(do, chunk))
    _, outs = lax.scan(step, jnp.zeros((bh, dk, v.shape[2]), _F32), xs,
                       reverse=True)
    return tuple(_unchunked(x) for x in outs)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(q, k, v, g, beta, chunk, with_states):
    """The forward for the platform this program is compiled for."""
    return lax.platform_dependent(
        q, k, v, g, beta,
        tpu=lambda *a: _fwd_call(*a, chunk, with_states, interpret=False),
        cpu=lambda *a: _scan_fwd(*a, chunk, with_states))


@functools.partial(jax.jit, static_argnums=(8,))
def _backward(q, k, v, g, beta, states, t_invs, do, chunk):
    return lax.platform_dependent(
        q, k, v, g, beta, states, t_invs, do,
        tpu=lambda *a: _bwd_call(*a, chunk, interpret=False),
        cpu=lambda *a: _scan_bwd(*a, chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _delta3(q, k, v, g, beta, chunk):
    return _forward(q, k, v, g, beta, chunk, False)


def _delta3_fwd(q, k, v, g, beta, chunk):
    o, states, t_invs = _forward(q, k, v, g, beta, chunk, True)
    telemetry.gauge(
        "delta_rule_state_saved_bytes",
        help="bytes of chunk-boundary state one differentiated call of the "
             "gated delta rule keeps for its backward (the last call "
             "traced)").set(states.size * states.dtype.itemsize)
    telemetry.gauge(
        "delta_rule_inverse_saved_bytes",
        help="bytes of the chunks' (I + A)^{-1} one differentiated call of "
             "the gated delta rule hands to its backward (the last call "
             "traced)").set(t_invs.size * t_invs.dtype.itemsize)
    return o, (q, k, v, g, beta, states, t_invs)


def _delta3_bwd(chunk, res, do):
    q, k, v, g, beta, states, t_invs = res
    with telemetry.span("delta_rule.build", category="compile",
                        tags={"pass": "bwd"}):
        dq, dk, dv, dg, db = _backward(q, k, v, g, beta, states, t_invs, do,
                                       chunk)
    return dq, dk, dv, dg.astype(g.dtype), db.astype(beta.dtype)


_delta3.defvjp(_delta3_fwd, _delta3_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk=CHUNK):
    """o (B, H, T, d_v) of q, k (B, H, T, d_k), v (B, H, T, d_v) and g, beta
    (B, H, T); T a multiple of `chunk`, a power of two."""
    b, h, t, dk = q.shape
    dv = v.shape[3]
    if t % chunk or chunk & (chunk - 1):
        raise ValueError("gated_delta_rule: T=%d is no multiple of the chunk "
                         "%d, or the chunk no power of two" % (t, chunk))
    telemetry.gauge("delta_rule_chunk", help="tokens a chunk of the gated "
                    "delta rule (the last call traced)").set(chunk)
    telemetry.gauge("delta_rule_chunks_per_row",
                    help="chunks a row of the gated delta rule, the states "
                         "stepped through (the last call traced)"
                    ).set(t // chunk)
    with telemetry.span("delta_rule.build", category="compile",
                        tags={"pass": "fwd",
                              "shape": "%dx%dx%dx%dx%d" % (b, h, t, dk, dv)}):
        o = _delta3(q.reshape(b * h, t, dk), k.reshape(b * h, t, dk),
                    v.reshape(b * h, t, dv), g.reshape(b * h, t).astype(_F32),
                    beta.reshape(b * h, t), int(chunk))
    return o.reshape(b, h, t, dv)


def _delta_op(a, q, k, v, g, beta):
    return gated_delta_rule(q, k, v, g, beta, chunk=a.chunk)


register("_contrib_GatedDeltaRule", _delta_op,
         arg_names=["query", "key", "value", "g", "beta"],
         attrs={"chunk": CHUNK}, aliases=("gated_delta_rule",))
