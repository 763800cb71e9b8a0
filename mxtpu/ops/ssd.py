"""The selective state-space scan of Mamba-2 (SSD: Dao and Gu,
arXiv:2405.21060) as chunked Pallas TPU kernels, forward and backward.

Per head, with a state S in R^{N x P}, S_0 = 0, and per token t an input
x_t in R^P, a step dt_t > 0, a_t = dt_t A (A < 0 a number a head) and B_t,
C_t in R^N that a group of heads shares:

    S_t = exp(a_t) S_{t-1} + dt_t B_t x_t^T;   y_t = S_t^T C_t + D x_t

It is the gated delta rule's sibling (ops/delta_rule.py) without the delta
correction, so a chunk has no triangular solve. A row of T tokens is cut
into chunks of Q (128, as published); inside a chunk, with cs the running
sum of a from the chunk's start, L_tj = exp(cs_t - cs_j) for j <= t, and the
chunk's first state S:

    Y  = (L * C B^T) diag(dt) X + diag(e^cs) C S + D X
    S' = e^{cs_Q} S + B^T diag(e^{cs_Q - cs} dt) X

cs, L and the state are float32 whatever the operands' dtype, and every
exponent taken is <= 0 (a <= 0). Matrix operands are cast to the dtype of x
for the MXU.

The kernels read x and y as the projections leave them, (B, T, H P), and B
and C as (B, T, G N): a grid step is one group of H / G heads over one chunk
(grid (batch, groups, chunks)), so C B^T is taken once a group, B and C
cross HBM once a group, and nothing is repeated or transposed in HBM. Inside
a step the group's lanes are walked in tiles of 128 (two heads of 64): a
head's scalars are spread over its lanes by selects, its own L by masking
the other head's lanes out of the operand, so no slice is narrower than a
lane tile. The state of the group, (N, H/G P) float32, is carried in VMEM
scratch along the chunk axis. Under differentiation the one residual beside
the inputs is each chunk's first state. Backward (``jax.custom_vjp``): the
same grid walked from the last chunk to the first with dS carried in
scratch, each chunk recomputed from its saved first state and differentiated
by hand (`_chunk_bwd`). No per-token state reaches HBM in either direction.
dt and cs come in as rows (1, Q) a head and are turned into columns by the
delta rule's `_col`; the running sum itself and its transpose under
differentiation are taken outside the kernels, on (B, T, H) numbers.

Which program runs follows the platform the program is lowered for, as in
ops/delta_rule.py: the Mosaic kernels on ``tpu`` (there H/G x P must be a
multiple of 128, or G 1); on ``cpu`` the same chunk functions under
``lax.scan`` and ``vmap`` (the kernels run on the CPU only in tests, in
interpret mode, against that path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from .delta_rule import _ABT, _ATB, _F32, _col, _dot, _masks, _row
from .registry import register

CHUNK = 128
FWD_KERNEL_NAME = "mxtpu_ssd_fwd"
BWD_KERNEL_NAME = "mxtpu_ssd_bwd"


def _tiling(r, p):
    """(heads a lane tile, the tile's width, tiles) of a group of `r` heads
    of `p`: as many whole heads as 128 lanes hold."""
    hp = 1 if p >= 128 else max(k for k in range(1, r + 1)
                                if r % k == 0 and k * p <= 128)
    return hp, hp * p, r // hp


def _spread(vals, p, shape):
    """One value a head ((rows, 1) or (1, 1) each) over the heads' lanes of
    a tile: (rows, len(vals) * p)."""
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    out = jnp.broadcast_to(vals[0], shape)
    for k in range(1, len(vals)):
        out = jnp.where(lane >= k * p, jnp.broadcast_to(vals[k], shape), out)
    return out


def _mine(z, k, p, hp):
    """z with the lanes of the tile's other heads set to 0."""
    if hp == 1:
        return z
    lane = lax.broadcasted_iota(jnp.int32, z.shape, 1)
    return jnp.where((lane >= k * p) & (lane < (k + 1) * p), z, 0.0)


def _head(dt_row, cs_row):
    """What a chunk needs of one head's rows (1, Q): columns (Q, 1) of dt,
    e^cs, e^{cs_Q - cs} and their product with dt, e^{cs_Q} (1, 1), and the
    masked decays L (Q, Q)."""
    q = dt_row.shape[1]
    incl, _, eye = _masks(q)
    dt, cs = _col(dt_row, eye), _col(cs_row, eye)
    last = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    total = jnp.sum(jnp.where(last, cs_row, 0.0), axis=1, keepdims=True)
    # exponents are masked before exp: above the diagonal they are > 0
    decay = jnp.where(incl, jnp.exp(jnp.where(incl, cs - cs_row, 0.0)), 0.0)
    ew = jnp.exp(total - cs)
    return {"dt": dt, "eg": jnp.exp(cs), "ew": ew, "w": ew * dt,
            "a_end": jnp.exp(total), "L": decay}


def _tile_heads(dt, cs, t, hp):
    return [_head(dt[k:k + 1], cs[k:k + 1])
            for k in range(t * hp, (t + 1) * hp)]


def _chunk_fwd(s, x, dt, cs, bm, cm, d_row, p):
    """One chunk of one group: s (N, r P) float32 its first state, x (Q, r P),
    dt and cs (r, Q) float32, bm and cm (Q, N), d_row (1, r P), arrays or
    the kernels' refs (every operand is read a lane tile or a row at a
    time). Returns the lane tiles of y (float32) and of the chunk's last
    state."""
    q, cd, r = x.shape[0], x.dtype, dt.shape[0]
    hp, w, tiles = _tiling(r, p)
    bm, cm = bm[...], cm[...]
    g = _dot(cm, bm, _ABT)
    ys, ss = [], []
    for t in range(tiles):
        lanes = slice(t * w, (t + 1) * w)
        hs = _tile_heads(dt, cs, t, hp)
        xt, st = x[:, lanes].astype(_F32), s[:, lanes]
        xd = xt * _spread([h["dt"] for h in hs], p, (q, w))
        y = (_spread([h["eg"] for h in hs], p, (q, w))
             * _dot(cm, st.astype(cd)) + d_row[:, lanes] * xt)
        for k, h in enumerate(hs):
            y = y + _dot((h["L"] * g).astype(cd),
                         _mine(xd, k, p, hp).astype(cd))
        wx = (xt * _spread([h["w"] for h in hs], p, (q, w))).astype(cd)
        ys.append(y)
        ss.append(_spread([h["a_end"] for h in hs], p, (1, w)) * st
                  + _dot(bm, wx, _ATB))
    return ys, ss


def _chunk_bwd(s, x, dt, cs, bm, cm, d_row, dy, ds_next, p):
    """Gradients of one chunk of one group, recomputed from its first state
    `s`: (tiles of dx, rows (1, Q) of ddt and of dcs a head, dB, dC (Q, N),
    tiles of dD's row (1, w), tiles of ds). `dy` is the gradient of the
    chunk's output, `ds_next` of its last state (float32)."""
    q, cd, r = x.shape[0], x.dtype, dt.shape[0]
    hp, w, tiles = _tiling(r, p)
    incl, _, eye = _masks(q)
    last = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    bm, cm = bm[...], cm[...]
    g = _dot(cm, bm, _ABT)
    dg = jnp.zeros((q, q), _F32)
    dbm = jnp.zeros(bm.shape, _F32)
    dcm = jnp.zeros(cm.shape, _F32)
    dxs, ddts, dcss, dds, dss = [], [], [], [], []
    for t in range(tiles):
        lanes = slice(t * w, (t + 1) * w)
        hs = _tile_heads(dt, cs, t, hp)
        xt, dyt = x[:, lanes].astype(_F32), dy[:, lanes].astype(_F32)
        st, dst = s[:, lanes], ds_next[:, lanes]
        s_c, dsn_c = st.astype(cd), dst.astype(cd)
        dt_x = _spread([h["dt"] for h in hs], p, (q, w))
        eg_x = _spread([h["eg"] for h in hs], p, (q, w))
        w_x = _spread([h["w"] for h in hs], p, (q, w))
        xd = (xt * dt_x).astype(cd)
        # Y = sum_k (L_k * G) Xd_k + eg * (C S) + D X
        # S' = a_end S + B^T (w X)
        between = eg_x * _dot(cm, s_c)
        bds = _dot(bm, dsn_c)
        egdy = (eg_x * dyt).astype(cd)
        dcm = dcm + _dot(egdy, s_c, _ABT)
        dbm = dbm + _dot((xt * w_x).astype(cd), dsn_c, _ABT)
        dss.append(_spread([h["a_end"] for h in hs], p, (1, w)) * dst
                   + _dot(cm, egdy, _ATB))
        dxd = jnp.zeros((q, w), _F32)
        within = []
        for k, h in enumerate(hs):
            m = h["L"] * g
            dyk = _mine(dyt, k, p, hp).astype(cd)
            dxd = dxd + _dot(m.astype(cd), dyk, _ATB)
            dm = jnp.where(incl, _dot(dyk, xd, _ABT), 0.0)
            dg = dg + dm * h["L"]
            # L_tj = e^{cs_t - cs_j}: a row's sum for cs_t, less a column's
            dd = dm * m
            within.append(jnp.sum(dd, axis=1, keepdims=True)
                          - _col(jnp.sum(dd, axis=0, keepdims=True), eye))
        dxs.append(dt_x * dxd + d_row[:, lanes] * dyt + w_x * bds)
        dds.append(jnp.sum(dyt * xt, axis=0, keepdims=True))
        p1, p2, p3, p4 = dxd * xt, bds * xt, dyt * between, dst * st
        for k, h in enumerate(hs):
            r1, r2, r3 = (jnp.sum(_mine(z, k, p, hp), axis=1, keepdims=True)
                          for z in (p1, p2, p3))
            # w = e^{cs_Q - cs} dt;  eg = e^cs;  a_end = e^{cs_Q}
            r2w = r2 * h["w"]
            dtotal = (jnp.sum(r2w, keepdims=True)
                      + jnp.sum(_mine(p4, k, p, hp), keepdims=True)
                      * h["a_end"])
            ddts.append(_row(r1 + r2 * h["ew"], eye))
            dcss.append(_row(within[k] + r3 - r2w, eye)
                        + jnp.where(last, dtotal, 0.0))
    dg = dg.astype(cd)
    dcm = dcm + _dot(dg, bm)
    dbm = dbm + _dot(dg, cm, _ATB)
    return dxs, ddts, dcss, dbm, dcm, dds, dss


# ------------------------------------------------------------------ kernels
def _fwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref, y_ref, *refs, p):
    """One chunk of one group: y and, under differentiation, the chunk's
    first state; the state is carried in the scratch."""
    s_ref = refs[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    if len(refs) == 2:
        refs[0][0, 0, 0] = s_ref[...]
    ys, ss = _chunk_fwd(s_ref, x_ref.at[0], dt_ref.at[0, 0, 0],
                        cs_ref.at[0, 0, 0], b_ref.at[0], c_ref.at[0],
                        d_ref.at[0], p)
    w = ys[0].shape[1]
    for t, (y, s_next) in enumerate(zip(ys, ss)):
        y_ref[0, :, t * w:(t + 1) * w] = y.astype(y_ref.dtype)
        s_ref[:, t * w:(t + 1) * w] = s_next


def _bwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref, s_in, dy_ref,
                dx_ref, ddt_ref, dcs_ref, db_ref, dc_ref, dd_ref, ds_ref, *,
                p):
    """The same chunks, last to first, with dS carried in the scratch and
    dD's row summed over the chunks in its output block."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    dxs, ddts, dcss, dbm, dcm, dds, dss = _chunk_bwd(
        s_in.at[0, 0, 0], x_ref.at[0], dt_ref.at[0, 0, 0], cs_ref.at[0, 0, 0],
        b_ref.at[0], c_ref.at[0], d_ref.at[0], dy_ref.at[0], ds_ref, p)
    w = dxs[0].shape[1]
    for t, (dx, dd, ds) in enumerate(zip(dxs, dds, dss)):
        lanes = slice(t * w, (t + 1) * w)
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        dd_ref[0, 0, :, lanes] += dd
        ds_ref[:, lanes] = ds
    for k, (ddt, dcs) in enumerate(zip(ddts, dcss)):
        ddt_ref[0, 0, 0, k:k + 1, :] = ddt
        dcs_ref[0, 0, 0, k:k + 1, :] = dcs
    db_ref[0] = dbm.astype(db_ref.dtype)
    dc_ref[0] = dcm.astype(dc_ref.dtype)


def _specs(n, chunk, wide, r, state, reverse):
    """Block specs of (x or y, a group's rows of dt or cs, B or C, D's row,
    the states) over the grid (batch, groups, chunks)."""
    at = (lambda i: n - 1 - i) if reverse else (lambda i: i)
    xs = pl.BlockSpec((1, chunk, wide), lambda b, g, i: (b, at(i), g))
    rows = pl.BlockSpec((1, 1, 1, r, chunk),
                        lambda b, g, i: (b, g, at(i), 0, 0))
    bc = pl.BlockSpec((1, chunk, state), lambda b, g, i: (b, at(i), g))
    dr = pl.BlockSpec((1, 1, wide), lambda b, g, i: (g, 0, 0))
    st = pl.BlockSpec((1, 1, 1, state, wide),
                      lambda b, g, i: (b, g, at(i), 0, 0))
    return xs, rows, bc, dr, st


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_call(x, dt, cs, bm, cm, d_rows, p, with_states, interpret):
    b = x.shape[0]
    _, groups, n, r, chunk = dt.shape
    wide, state = r * p, bm.shape[2] // groups
    xs, rows, bc, dr, st = _specs(n, chunk, wide, r, state, False)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [xs]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct((b, groups, n, state, wide),
                                              _F32))
        out_specs.append(st)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p), out_shape=out_shape,
        grid=(b, groups, n), in_specs=[xs, rows, rows, bc, bc, dr],
        out_specs=out_specs, scratch_shapes=[pltpu.VMEM((state, wide), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret, name=FWD_KERNEL_NAME,
    )(x, dt, cs, bm, cm, d_rows)
    return tuple(out) if with_states else out[0]


def _bwd_call(x, dt, cs, bm, cm, d_rows, states, dy, p, interpret):
    b = x.shape[0]
    _, groups, n, r, chunk = dt.shape
    wide, state = r * p, bm.shape[2] // groups
    xs, rows, bc, dr, st = _specs(n, chunk, wide, r, state, True)
    row_shape = jax.ShapeDtypeStruct(dt.shape, _F32)
    dd = pl.BlockSpec((1, 1, 1, wide), lambda b_, g, i: (b_, g, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), row_shape,
                   row_shape, jax.ShapeDtypeStruct(bm.shape, bm.dtype),
                   jax.ShapeDtypeStruct(cm.shape, cm.dtype),
                   jax.ShapeDtypeStruct((b, groups, 1, wide), _F32)],
        grid=(b, groups, n), in_specs=[xs, rows, rows, bc, bc, dr, st, xs],
        out_specs=[xs, rows, rows, bc, bc, dd],
        scratch_shapes=[pltpu.VMEM((state, wide), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret, name=BWD_KERNEL_NAME,
    )(x, dt, cs, bm, cm, d_rows, states, dy)


# ------------------------------------------- the same chunks without Mosaic
def _by_chunk(z, groups, chunk):
    """(B, T, G c) -> (T / Q, B, G, Q, c): chunks first, for `lax.scan`."""
    b, t, c = z.shape
    z = z.reshape(b, t // chunk, chunk, groups, c // groups)
    return jnp.transpose(z, (1, 0, 3, 2, 4))


def _by_row(z):
    """`_by_chunk` undone."""
    n, b, groups, chunk, c = z.shape
    return jnp.transpose(z, (1, 0, 3, 2, 4)).reshape(b, n * chunk, groups * c)


def _scan_fwd(x, dt, cs, bm, cm, d_rows, p, with_states):
    b = x.shape[0]
    _, groups, _, r, chunk = dt.shape
    state = bm.shape[2] // groups

    def group(s, xc, dtc, csc, bc, cc, d_row):
        ys, ss = _chunk_fwd(s, xc, dtc, csc, bc, cc, d_row, p)
        return jnp.concatenate(ys, axis=1), jnp.concatenate(ss, axis=1)

    body = jax.vmap(jax.vmap(group, in_axes=(0,) * 6 + (0,)),
                    in_axes=(0,) * 6 + (None,))

    def step(s, xs):
        y, s_next = body(s, *xs, d_rows)
        return s_next, (y.astype(x.dtype), s)

    xs = (_by_chunk(x, groups, chunk), jnp.moveaxis(dt, 2, 0),
          jnp.moveaxis(cs, 2, 0), _by_chunk(bm, groups, chunk),
          _by_chunk(cm, groups, chunk))
    _, (y, states) = lax.scan(
        step, jnp.zeros((b, groups, state, r * p), _F32), xs)
    y = _by_row(y)
    return (y, jnp.moveaxis(states, 0, 2)) if with_states else y


def _scan_bwd(x, dt, cs, bm, cm, d_rows, states, dy, p):
    b = x.shape[0]
    _, groups, _, r, chunk = dt.shape
    state = bm.shape[2] // groups

    def group(s, xc, dtc, csc, bc, cc, d_row, dyc, ds_next):
        dxs, ddts, dcss, dbm, dcm, dds, dss = _chunk_bwd(
            s, xc, dtc, csc, bc, cc, d_row, dyc, ds_next, p)
        cat = functools.partial(jnp.concatenate, axis=1)
        return (cat(dxs), jnp.concatenate(ddts, axis=0),
                jnp.concatenate(dcss, axis=0), dbm, dcm, cat(dds), cat(dss))

    # the rows come out tile by tile, head by head: the heads' own order
    body = jax.vmap(jax.vmap(group), in_axes=(0,) * 6 + (None, 0, 0))

    def step(ds, xs):
        sc, xc, dtc, csc, bc, cc, dyc = xs
        dx, ddt, dcs, dbm, dcm, dd, ds = body(sc, xc, dtc, csc, bc, cc,
                                              d_rows, dyc, ds)
        return ds, (dx.astype(x.dtype), ddt, dcs, dbm.astype(bm.dtype),
                    dcm.astype(cm.dtype), dd)

    xs = (jnp.moveaxis(states, 2, 0), _by_chunk(x, groups, chunk),
          jnp.moveaxis(dt, 2, 0), jnp.moveaxis(cs, 2, 0),
          _by_chunk(bm, groups, chunk), _by_chunk(cm, groups, chunk),
          _by_chunk(dy, groups, chunk))
    _, (dx, ddt, dcs, dbm, dcm, dd) = lax.scan(
        step, jnp.zeros((b, groups, state, r * p), _F32), xs, reverse=True)
    return (_by_row(dx), jnp.moveaxis(ddt, 0, 2), jnp.moveaxis(dcs, 0, 2),
            _by_row(dbm), _by_row(dcm), jnp.sum(dd, axis=0))


@functools.partial(jax.jit, static_argnums=(6, 7))
def _forward(x, dt, cs, bm, cm, d_rows, p, with_states):
    """The forward for the platform this program is compiled for."""
    return lax.platform_dependent(
        x, dt, cs, bm, cm, d_rows,
        tpu=lambda *a: _fwd_call(*a, p, with_states, interpret=False),
        cpu=lambda *a: _scan_fwd(*a, p, with_states))


@functools.partial(jax.jit, static_argnums=(8,))
def _backward(x, dt, cs, bm, cm, d_rows, states, dy, p):
    return lax.platform_dependent(
        x, dt, cs, bm, cm, d_rows, states, dy,
        tpu=lambda *a: tuple(_bwd_call(*a, p, interpret=False)),
        cpu=lambda *a: _scan_bwd(*a, p))


def _running(a):
    """cs: the running sum of a (B, G, T/Q, r, Q) inside each chunk."""
    return jnp.cumsum(a, axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, a, bm, cm, d_rows, p):
    return _forward(x, dt, _running(a), bm, cm, d_rows, p, False)


def _ssd_fwd(x, dt, a, bm, cm, d_rows, p):
    y, states = _forward(x, dt, _running(a), bm, cm, d_rows, p, True)
    telemetry.gauge(
        "ssd_state_saved_bytes",
        help="bytes of chunk-boundary state one differentiated call of the "
             "state-space scan keeps for its backward (the last call traced)"
        ).set(states.size * states.dtype.itemsize)
    return y, (x, dt, a, bm, cm, d_rows, states)


def _ssd_bwd(p, res, dy):
    x, dt, a, bm, cm, d_rows, states = res
    with telemetry.span("ssd.build", category="compile",
                        tags={"pass": "bwd"}):
        dx, ddt, dcs, dbm, dcm, dd = _backward(
            x, dt, _running(a), bm, cm, d_rows, states, dy, p)
    # cs_t = sum_{i<=t} a_i: da_t = sum_{i>=t} dcs_i
    da = jnp.flip(jnp.cumsum(jnp.flip(dcs, -1), axis=-1), -1)
    return (dx, ddt.astype(dt.dtype), da.astype(a.dtype), dbm, dcm,
            jnp.sum(dd, axis=0))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a_log, bm, cm, d, chunk=CHUNK):
    """y (B, T, H, P) of x (B, T, H, P), dt (B, T, H) the steps (after their
    softplus), a_log (H,) with A = -exp(a_log), bm and cm (B, T, G, N), G
    dividing H (head j reads group j // (H / G)), and d (H,) the skip's
    weights; T a multiple of `chunk`, a power of two."""
    b, t, h, p = x.shape
    groups, state = bm.shape[2], bm.shape[3]
    if t % chunk or chunk & (chunk - 1) or h % groups:
        raise ValueError("ssd_scan: T=%d is no multiple of the chunk %d, the "
                         "chunk no power of two, or %d heads not in %d groups"
                         % (t, chunk, h, groups))
    r, n = h // groups, t // chunk
    telemetry.gauge("ssd_chunks_per_row", help="chunks a row of the "
                    "state-space scan, the states stepped through (the last "
                    "call traced)").set(n)

    def rows(z):    # (B, T, H) -> (B, G, T/Q, r, Q)
        z = z.astype(_F32).reshape(b, n, chunk, groups, r)
        return jnp.transpose(z, (0, 3, 1, 4, 2))

    dt = dt.astype(_F32)
    a = dt * -jnp.exp(a_log.astype(_F32))
    d_rows = jnp.repeat(d.astype(_F32), p).reshape(groups, 1, r * p)
    with telemetry.span("ssd.build", category="compile",
                        tags={"pass": "fwd", "shape": "%dx%dx%dx%dx%dx%d" % (
                            b, t, h, p, groups, state)}):
        y = _ssd(x.reshape(b, t, h * p), rows(dt), rows(a),
                 bm.reshape(b, t, groups * state),
                 cm.reshape(b, t, groups * state), d_rows, int(p))
    return y.reshape(b, t, h, p)


def _ssd_op(a, x, dt, a_log, bm, cm, d):
    """The selective state-space scan of Mamba-2: per head, with a state S
    (N x P) starting at 0, S = exp(dt_t A) S + dt_t B_t x_t^T and
    y_t = S^T C_t + D x_t, A = -exp(A_log). data (B, T, H, P), dt (B, T, H)
    (after its softplus), A_log and D (H,), B and C (B, T, G, N), G dividing
    H; T a multiple of ``chunk``. Chunked Pallas kernels on the TPU
    (``mxtpu_ssd_fwd`` / ``mxtpu_ssd_bwd``), the op's own backward."""
    return ssd_scan(x, dt, a_log, bm, cm, d, chunk=a.chunk)


register("_contrib_SSDScan", _ssd_op,
         arg_names=["data", "dt", "A_log", "B", "C", "D"],
         attrs={"chunk": CHUNK}, aliases=("ssd_scan",))
