"""The delta rule with a decay a CHANNEL of the key (Kimi Delta
Attention, arXiv:2510.26692), as one operator.

Per head, with a log decay `g_t <= 0` a key channel `[K]` and a write
strength `beta_t` in (0, 1), the state `S` `[K, V]` float32:

    S'  = Diag(exp(g_t)) S_{t-1}          (row c of the state times exp(g_t[c]))
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T        o_t = S_t^T q_t

`q, k: [B, T, H, K]` (the caller has normalised and scaled them), `v:
[B, T, H, V]`, `g: [B, T, H, K]` and `beta: [B, T, H]` float32. With g
equal over a head's channels this IS `ops/gated_delta.py`'s rule; with a
vector the decay between two rows no longer factors out of `k_i . k_j`.
In chunks of C positions, with `Y` `[C, K]` the running sum of g inside
a chunk and `S` the state that enters it,

    M_ij = sum_c k_ic k_jc exp(Y_ic - Y_jc)            P_ij = sum_c q_ic k_jc exp(Y_ic - Y_jc)
    A   = strict_tril(beta_i M_ij)                     Tm = (I + A)^-1
    V'  = Tm (beta o (V - (e^Y o K) S))
    O   = (e^Y o Q) S + tril(P) V'
    S'' = Diag(e^{Y_C}) S + (e^{Y_C - Y} o K)^T V'

— `gated_delta.py`'s WY form with its `D_ij` taken inside the sum over
the channels; `Tm` is that module's own `_inverses` (by import: the
doubling, two heads side by side in the lanes).

Exponents are taken of DIFFERENCES only, each <= 0: `exp(-Y_j)` alone
overflows (a channel at A = 16 loses a thousand in a chunk). M and P are
built in row blocks of `SUB` = 16. Block I against the rows BEFORE it,
through a reference row r, the block's first: `(k_i o e^{Y_i - Y_r}) .
(k_j o e^{Y_r - Y_j})`, both factors at most one for j < r <= i — one
`[2 SUB, K] [K, C]` product a block for M and P together, the right-hand
factor zero from row r on. Block I against itself (the diagonal blocks)
by explicit differences, one column at a time on the vector unit: column
j of the block is `sum_c x_ic k_jc exp(Y_ic - Y_jc)` over the rows i >=
j, the rows above under -inf before the exponential (a loop over the
block's columns, unrolled on the chip, where the rolled loop measured
4.6 times slower: PERF.md section 6, PR 61; rolled in interpret mode,
whose compile it shortens five times). A chunk that forgets everything
gives zeros, not NaNs, and nothing is clamped.

Two forms of the same function. `kda_xla` is the chunked equations in
plain `jnp` (every pairwise difference formed, `[.., C, C, H, K]`: for
small sizes; one triangular solve a chunk and head, a `lax.scan` carries
the state), differentiated by JAX, and what the kernels are tested
against. `kda` has a `custom_vjp` over two Mosaic kernels, named so the
device trace carries them: `kda_fwd` (the forward pass, and its
rematerialised copy) and `kda_bwd`. Both have the grid (sequence, PAIR
of heads, chunk) — two heads a grid step while there are two, so that
their inverses run two to a product — laid out as `ops/gated_delta.py`
is: chunks the innermost axis, walked in order, the heads' states `[2,
K, V]` float32 in VMEM scratch; the backward walks the chunks in REVERSE
with the state's gradient in that scratch and reads each chunk's
entering state, which the forward saves when it is differentiated (`[B,
T / C, H, K, V]` float32). The backward recomputes the forward's values
and the decays of each block from the same differences.

The running sums are made OUTSIDE the kernels, in XLA (a float32
`cumsum` over a chunk, differentiated by it): `Y` `[B, T, H K]` beside
q and k, and a chunk's last row once more as COLUMNS `[.., K, heads]`
(what scales the state's rows; a `[1, K]` row cannot be laid along the
sublanes in the kernel), beta as columns `[.., C, heads]`. The backward
returns the sums' gradient in both layouts. The products run in the
inputs' dtype with float32 accumulation; the sums, every exponential,
the diagonal blocks, Tm and the state are float32. The kernels are
independent over the batch (`ops/partition.py`). `T % chunk != 0`
raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private.accelerator import is_tpu
from ray_tpu.ops.gated_delta import (_F32, _NN, _NT, _PARAMS, _TN, CHUNK,
                                     _dot, _inverses)
from ray_tpu.ops.partition import over_leading_dim

SUB = 16        # rows of a block of M and P


def heads_a_step(heads: int) -> int:
    """The heads a grid step holds: two while the heads pair up (their
    chunk inverses then run two to a product), else one."""
    return 1 if heads % 2 else 2


def _check(q, k, v, g, beta, chunk: int):
    batch, t, heads, _ = v.shape
    if t % chunk:
        raise ValueError(f"the delta rule walks whole chunks: T = {t} is "
                         f"not a multiple of {chunk}")
    if q.shape != k.shape or q.shape[:3] != (batch, t, heads) \
            or g.shape != q.shape or beta.shape != (batch, t, heads):
        raise ValueError(
            f"kda: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, "
            f"beta {beta.shape} are not [B, T, H, K] twice, [B, T, H, V], "
            "[B, T, H, K] and [B, T, H]")


def kda_xla(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked equations in plain `jnp`, float32 throughout, from a
    zero state -> o [B, T, H, V] in v's dtype."""
    _check(q, k, v, g, beta, chunk)
    batch, t, h, dv = v.shape
    dk = q.shape[3]
    nc, f32 = t // chunk, functools.partial(jnp.asarray, dtype=_F32)
    qc, kc, gc = (f32(z).reshape(batch, nc, chunk, h, dk) for z in (q, k, g))
    vc = f32(v).reshape(batch, nc, chunk, h, dv)
    bc = f32(beta).reshape(batch, nc, chunk, h)
    y = jnp.cumsum(gc, axis=2)                            # [B, nc, C, H, K]
    total = y[:, :, -1]                                   # [B, nc, H, K]
    at = jnp.arange(chunk)
    tri = (at[:, None] >= at[None, :])[None, None, :, :, None, None]
    strict = (at[:, None] > at[None, :])[None, None, :, :, None]
    # every pairwise decay, [B, nc, i, j, H, K]
    decay = jnp.exp(jnp.where(tri, y[:, :, :, None] - y[:, :, None, :],
                              -jnp.inf))
    kk = jnp.einsum("bcihd,bcjhd,bcijhd->bcijh", kc, kc, decay)
    a = jnp.where(strict, bc[:, :, :, None] * kk, 0.0)
    # W and U of every chunk: one unit-lower-triangular solve a chunk
    # and head, [e^Y o K | V] o beta on the right
    rhs = bc[..., None] * jnp.concatenate([jnp.exp(y) * kc, vc], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        jnp.moveaxis(a, 4, 2) + jnp.eye(chunk), jnp.moveaxis(rhs, 3, 2),
        lower=True, unit_diagonal=True)                   # [B, nc, H, C, .]
    w, u = solved[..., :dk], solved[..., dk:]
    scores = jnp.moveaxis(
        jnp.einsum("bcihd,bcjhd,bcijhd->bcijh", qc, kc, decay), 4, 2)
    k_out = jnp.moveaxis(jnp.exp(total[:, :, None] - y) * kc, 3, 2)
    q_in = jnp.moveaxis(jnp.exp(y) * qc, 3, 2)

    def step(state, part):
        w, u, scores, k_out, q_in, keep = part
        fresh = u - jnp.einsum("bhik,bhkv->bhiv", w, state)
        o = jnp.einsum("bhik,bhkv->bhiv", q_in, state) \
            + jnp.einsum("bhij,bhjv->bhiv", scores, fresh)
        state = keep[..., None] * state \
            + jnp.einsum("bhik,bhiv->bhkv", k_out, fresh)
        return state, o

    _, o = lax.scan(step, jnp.zeros((batch, h, dk, dv), _F32), tuple(
        z.swapaxes(0, 1) for z in (w, u, scores, k_out, q_in,
                                   jnp.exp(total))))
    # [nc, B, H, C, V] -> [B, T, H, V]
    return o.transpose(1, 0, 3, 2, 4).reshape(batch, t, h, dv).astype(
        v.dtype)


def _row(x, at, j):
    """Row j of x [rows, K] as [1, K] (`at`: the rows' iota)."""
    return jnp.where(at == j, x, 0.0).sum(0, keepdims=True)


def _block_parts(y, kf, r: int, cast):
    """Block [r, r + SUB) against the rows before it, through row r:
    (exp(Y_i - Y_r) [SUB, K], exp(Y_r - Y_j) [C, K], zero from row r on,
    and k times it in the products' dtype)."""
    yb = y[r:r + SUB]
    before = lax.broadcasted_iota(jnp.int32, y.shape, 0) < r
    left = jnp.exp(yb - yb[0:1])
    right = jnp.exp(jnp.where(before, yb[0:1] - y, -jnp.inf))
    return left, right, cast(kf * right)


def _column_decay(yb, kb, at, j):
    """Column j of a diagonal block: exp(Y_i - Y_j) over the rows i >= j
    of the block (zero above), and k_j times it, [SUB, K] both."""
    e = jnp.exp(jnp.where(at >= j, yb - _row(yb, at, j), -jnp.inf))
    return e, _row(kb, at, j) * e


def _decayed_products(qf, kf, y, cast):
    """M and P [C, C] float32, every entry with i >= j filled (the
    entries above the diagonal are left to the callers' masks)."""
    c = y.shape[0]
    at = lax.broadcasted_iota(jnp.int32, (SUB, y.shape[1]), 0)
    col = lax.broadcasted_iota(jnp.int32, (SUB, c), 1)
    m_rows, p_rows = [], []
    for r in range(0, c, SUB):
        yb, kb, qb = y[r:r + SUB], kf[r:r + SUB], qf[r:r + SUB]
        def column(j, mp):
            _, t = _column_decay(yb, kb, at, j)
            return tuple(
                jnp.where(col == r + j, (x * t).sum(1, keepdims=True), z)
                for x, z in zip((kb, qb), mp))

        m, p = lax.fori_loop(0, SUB, column,
                             (jnp.zeros((SUB, c), _F32),) * 2,
                             unroll=is_tpu())
        if r:
            left, _, k_right = _block_parts(y, kf, r, cast)
            both = _dot(cast(jnp.concatenate([kb * left, qb * left])),
                        k_right, _NT)
            m, p = m + both[:SUB], p + both[SUB:]
        m_rows.append(m)
        p_rows.append(p)
    return jnp.concatenate(m_rows), jnp.concatenate(p_rows)


def _decayed_products_bwd(qf, kf, y, dm, dp, cast):
    """From dM and dP [C, C] (masked: zero where the forward's were) ->
    (the gradient that reaches q, k as ROWS of the pairs, k as their
    COLUMNS), each [C, K]: `gq_ic = sum_j dP_ij k_jc E_ijc`, `gk_ic =
    sum_j dM_ij k_jc E_ijc`, `gc_jc = sum_i (dM_ij k_ic + dP_ij q_ic)
    E_ijc`. The sums' gradient is `k o gk + q o gq - k o gc`."""
    c, width = y.shape
    at = lax.broadcasted_iota(jnp.int32, (SUB, width), 0)
    col = lax.broadcasted_iota(jnp.int32, (SUB, c), 1)
    gk_rows, gq_rows, gc_rows = [], [], []
    gc_before = jnp.zeros((c, width), _F32)
    for r in range(0, c, SUB):
        yb, kb, qb = y[r:r + SUB], kf[r:r + SUB], qf[r:r + SUB]
        dmb, dpb = dm[r:r + SUB], dp[r:r + SUB]
        def column(j, g):
            gk, gq, gc = g
            e, t = _column_decay(yb, kb, at, j)
            m = jnp.where(col == r + j, dmb, 0.0).sum(1, keepdims=True)
            p = jnp.where(col == r + j, dpb, 0.0).sum(1, keepdims=True)
            return gk + m * t, gq + p * t, jnp.where(
                at == j, ((m * kb + p * qb) * e).sum(0, keepdims=True), gc)

        gk, gq, gc = lax.fori_loop(
            0, SUB, column, (jnp.zeros((SUB, width), _F32),) * 3,
            unroll=is_tpu())
        if r:
            left, right, k_right = _block_parts(y, kf, r, cast)
            both = _dot(cast(jnp.concatenate([dmb, dpb])), k_right, _NN)
            gk, gq = gk + left * both[:SUB], gq + left * both[SUB:]
            gc_before = gc_before + right * (
                _dot(cast(dmb), cast(kb * left), _TN)
                + _dot(cast(dpb), cast(qb * left), _TN))
        gk_rows.append(gk)
        gq_rows.append(gq)
        gc_rows.append(gc)
    return jnp.concatenate(gq_rows), jnp.concatenate(gk_rows), \
        jnp.concatenate(gc_rows) + gc_before


def _head_parts(j: int, q_ref, k_ref, y_ref, bc_ref, dk: int, masks):
    """Head j of a grid step: q, k, Y float32 [C, K], beta [C, 1], M
    under the strict mask, P under the causal one, A."""
    tri, strict = masks
    lanes = pl.ds(j * dk, dk)
    qf, kf = q_ref[:, lanes].astype(_F32), k_ref[:, lanes].astype(_F32)
    y, beta = y_ref[:, lanes], bc_ref[:, j:j + 1]
    m, p = _decayed_products(
        qf, kf, y, functools.partial(jnp.asarray, dtype=k_ref.dtype))
    m, p = jnp.where(strict, m, 0.0), jnp.where(tri, p, 0.0)
    return qf, kf, y, beta, m, p, beta * m


def _masks(c: int):
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return (row >= col, row > col), (row == col).astype(_F32)


def _fwd_kernel(q_ref, k_ref, v_ref, y_ref, tc_ref, bc_ref, o_ref, *rest,
                dk: int, dv: int):
    state = rest[-1]                   # scratch [heads, K, V] float32
    entering = rest[0] if len(rest) == 2 else None

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    c = q_ref.shape[0]
    masks, eye = _masks(c)
    cast = functools.partial(jnp.asarray, dtype=k_ref.dtype)
    parts = [_head_parts(j, q_ref, k_ref, y_ref, bc_ref, dk, masks)
             for j in range(state.shape[0])]
    for j, inv in enumerate(_inverses([p[-1] for p in parts], eye)):
        qf, kf, y, beta, _, p, _ = parts[j]
        lanes = pl.ds(j * dv, dv)
        s = state[j]
        if entering is not None:
            entering[j] = s
        sc, e_y = cast(s), jnp.exp(y)
        rhs = beta * (v_ref[:, lanes].astype(_F32)
                      - _dot(cast(kf * e_y), sc, _NN))
        fresh = cast(_dot(cast(inv), cast(rhs), _NN))
        o = _dot(cast(qf * e_y), sc, _NN) + _dot(cast(p), fresh, _NN)
        o_ref[:, lanes] = o.astype(o_ref.dtype)
        state[j] = jnp.exp(tc_ref[:, j:j + 1]) * s + _dot(
            cast(kf * jnp.exp(y[c - 1:c] - y)), fresh, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, y_ref, tc_ref, bc_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dy_ref, dtc_ref, dbc_ref, dstate,
                *, dk: int, dv: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    c = q_ref.shape[0]
    heads = dstate.shape[0]
    masks, eye = _masks(c)
    tri, strict = masks
    cast = functools.partial(jnp.asarray, dtype=k_ref.dtype)
    last = lax.broadcasted_iota(jnp.int32, (c, dk), 0) == c - 1
    at_col = lax.broadcasted_iota(jnp.int32, (c, heads), 1)
    at_key = lax.broadcasted_iota(jnp.int32, (dk, heads), 1)
    dbeta_c = jnp.zeros((c, heads), _F32)
    dtotal_c = jnp.zeros((dk, heads), _F32)
    parts = [_head_parts(j, q_ref, k_ref, y_ref, bc_ref, dk, masks)
             for j in range(heads)]
    for j, inv in enumerate(_inverses([p[-1] for p in parts], eye)):
        qf, kf, y, beta, m, p, _ = parts[j]
        keys, lanes = pl.ds(j * dk, dk), pl.ds(j * dv, dv)
        s, ds = s_ref[j], dstate[j]
        sc, dsc = cast(s), cast(ds)
        e_y, w = jnp.exp(y), jnp.exp(y[c - 1:c] - y)
        keep = jnp.exp(tc_ref[:, j:j + 1])                   # [K, 1]
        k_in, q_in, k_out = kf * e_y, qf * e_y, kf * w
        # the forward's own values again
        inner = v_ref[:, lanes].astype(_F32) - _dot(cast(k_in), sc, _NN)
        fresh = _dot(cast(inv), cast(beta * inner), _NN)     # V' [C, V]
        do = do_ref[:, lanes].astype(_F32)
        # V' = Tm R feeds the output and the next state
        dfresh = _dot(cast(p), cast(do), _TN) + _dot(cast(k_out), dsc, _NN)
        dp = jnp.where(tri, _dot(cast(do), cast(fresh), _NT), 0.0)
        dr = _dot(cast(inv), cast(dfresh), _TN)              # Tm^T dV'
        da = jnp.where(strict, -_dot(cast(dr), cast(fresh), _NT), 0.0)
        dinner = beta * dr
        dv_ref[:, lanes] = dinner.astype(dv_ref.dtype)
        dk_in = -_dot(cast(dinner), sc, _NT)                 # [C, K]
        dq_in = _dot(cast(do), sc, _NT)
        dk_out = _dot(cast(fresh), dsc, _NT)
        gq, gk, gc = _decayed_products_bwd(qf, kf, y, beta * da, dp, cast)
        dq_ref[:, keys] = (dq_in * e_y + gq).astype(dq_ref.dtype)
        dk_ref[:, keys] = (dk_in * e_y + dk_out * w + gk + gc).astype(
            dk_ref.dtype)
        # Y: exp(Y) on the state's parts, exp(Y_C - Y) on what enters
        # the next state (its Y_C is row C - 1), the pairs' differences
        moved = dk_out * k_out
        dy_ref[:, keys] = dk_in * k_in + dq_in * q_in - moved \
            + jnp.where(last, moved.sum(0, keepdims=True), 0.0) \
            + kf * (gk - gc) + qf * gq
        dtotal_c = jnp.where(
            at_key == j, keep * (s * ds).sum(1, keepdims=True), dtotal_c)
        dbeta_c = jnp.where(
            at_col == j, (da * m).sum(1, keepdims=True)
            + (dr * inner).sum(1, keepdims=True), dbeta_c)
        dstate[j] = keep * ds + _dot(cast(q_in), cast(do), _TN) \
            - _dot(cast(k_in), cast(dinner), _TN)
    dtc_ref[...] = dtotal_c
    dbc_ref[...] = dbeta_c


def _specs(chunk: int, heads: int, dk: int, dv: int, at):
    """Block specs of one grid step's [C, heads * K] of q (k, Y, their
    gradients), its [C, heads * V] of v (o, do, dv), the chunk's last
    sums as columns [K, heads], beta as columns [C, heads], and the
    [heads, K, V] states, the chunk index given by `at(c)`."""
    def cols(rows):
        return pl.BlockSpec((None, None, None, rows, heads),
                            lambda i, g, c: (i, at(c), g, 0, 0))

    return {
        "q": pl.BlockSpec((None, chunk, heads * dk),
                          lambda i, g, c: (i, at(c), g)),
        "v": pl.BlockSpec((None, chunk, heads * dv),
                          lambda i, g, c: (i, at(c), g)),
        "total": cols(dk), "beta": cols(chunk),
        "state": pl.BlockSpec((None, None, heads, dk, dv),
                              lambda i, g, c: (i, at(c), g, 0, 0))}


def _sizes(v, total_c):
    batch, nc, groups, dk, heads = total_c.shape
    return batch, nc, groups, heads, v.shape[1] // nc, dk, \
        v.shape[2] // (groups * heads)


def _fwd_call(q, k, v, y, total_c, beta_c, *, save: bool):
    batch, nc, groups, heads, chunk, dk, dv = _sizes(v, total_c)
    s = _specs(chunk, heads, dk, dv, lambda c: c)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [s["v"]]
    if save:
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, nc, groups * heads, dk, dv), _F32))
        out_specs.append(s["state"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, dk=dk, dv=dv),
        grid=(batch, groups, nc),
        in_specs=[s["q"], s["q"], s["v"], s["q"], s["total"], s["beta"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        compiler_params=_PARAMS, interpret=not is_tpu(), name="kda_fwd",
    )(q, k, v, y, total_c, beta_c)
    return tuple(out) if save else out[0]


def _bwd_call(q, k, v, y, total_c, beta_c, entering, do):
    batch, nc, groups, heads, chunk, dk, dv = _sizes(v, total_c)
    s = _specs(chunk, heads, dk, dv, lambda c: nc - 1 - c)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dk=dk, dv=dv),
        grid=(batch, groups, nc),
        in_specs=[s["q"], s["q"], s["v"], s["q"], s["total"], s["beta"],
                  s["state"], s["v"]],
        out_specs=[s["q"], s["q"], s["v"], s["q"], s["total"], s["beta"]],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(y.shape, _F32),
            jax.ShapeDtypeStruct(total_c.shape, _F32),
            jax.ShapeDtypeStruct(beta_c.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        compiler_params=_PARAMS, interpret=not is_tpu(), name="kda_bwd",
    )(q, k, v, y, total_c, beta_c, entering, do)


_SPLIT = (True,) * 6


@jax.custom_vjp
def _rule(q, k, v, y, total_c, beta_c):
    """The kernels' own layout: q, k [B, T, H * K]; v [B, T, H * V]; y
    [B, T, H * K] the running sums of g inside each chunk; total_c [B,
    T / C, H / heads, K, heads] a chunk's last sums as columns; beta_c
    [B, T / C, H / heads, C, heads], float32 -> o like v."""
    return over_leading_dim(functools.partial(_fwd_call, save=False),
                            _SPLIT)(q, k, v, y, total_c, beta_c)


def _rule_fwd(q, k, v, y, total_c, beta_c):
    o, entering = over_leading_dim(functools.partial(_fwd_call, save=True),
                                   _SPLIT)(q, k, v, y, total_c, beta_c)
    return o, (q, k, v, y, total_c, beta_c, entering)


def _rule_bwd(res, do):
    return over_leading_dim(_bwd_call, _SPLIT + (True, True))(*res, do)


_rule.defvjp(_rule_fwd, _rule_bwd)


def kda(q, k, v, g, beta, chunk: int = CHUNK):
    """q, k: [B, T, H, K], normalised and scaled; v: [B, T, H, V]; g
    (the log decay a key channel, <= 0): [B, T, H, K] and beta: [B, T,
    H] float32 -> o [B, T, H, V] in v's dtype, from a zero state. The
    layouts the kernels read — the running sums of g inside a chunk, a
    chunk's last sums and beta as columns, two heads together — are made
    here, in XLA, and differentiated by it."""
    _check(q, k, v, g, beta, chunk)
    batch, t, h, dv = v.shape
    dk = q.shape[3]
    nc, heads = t // chunk, heads_a_step(h)
    y = jnp.cumsum(g.astype(_F32).reshape(batch, nc, chunk, h, dk), axis=2)

    def columns(x):             # [B, nc, rows, H] -> [B, nc, H / heads, rows, heads]
        rows = x.shape[2]
        return x.reshape(batch, nc, rows, h // heads, heads).swapaxes(2, 3)

    o = _rule(q.reshape(batch, t, h * dk), k.reshape(batch, t, h * dk),
              v.reshape(batch, t, h * dv), y.reshape(batch, t, h * dk),
              columns(y[:, :, -1].swapaxes(2, 3)),
              columns(beta.astype(_F32).reshape(batch, nc, chunk, h)))
    return o.reshape(batch, t, h, dv)
