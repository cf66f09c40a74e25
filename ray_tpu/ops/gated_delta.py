"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464), as one
operator.

Per value head, with a log decay `g_t <= 0` and a write strength
`beta_t` in (0, 1), the state `S` `[K, V]` float32:

    S'  = exp(g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T        o_t = S_t^T q_t

`q, k: [B, T, G, K]` (the caller has normalised and scaled them), `v:
[B, T, H, V]`, `g, beta: [B, T, H]` float32; key head j serves the value
heads `j H / G .. (j + 1) H / G - 1`. The update READS the state (`v_t -
S'^T k_t`), so this is not `ops/ssd.py` with other numbers: in chunks of
C positions, with `y` the running sum of g inside a chunk, `D_ij =
exp(y_i - y_j)` for i >= j and `S` the state that enters the chunk,

    A   = strict_tril(beta_i (k_i . k_j) D_ij)         Tm = (I + A)^-1
    V'  = Tm (beta o (V - e^y o (K S)))
    O   = e^y o (Q S) + tril(Q K^T o D) V'
    S'' = e^{y_C} S + (e^{y_C - y} o K)^T V'

which is the library's WY form, `W = Tm (beta o e^y o K)`, `U = Tm (beta
o V)`, `V' = U - W S`, with the state's product taken before the
triangular solve (one product of `[C, C]` instead of two).

Two forms of the same function. `gated_delta_xla` is the WY form in
plain `jnp` (W and U of every chunk by one batched triangular solve, a
`lax.scan` carries the state over the chunks), differentiated by JAX,
and what the kernels are tested against; it takes an entering state.
`gated_delta` has a `custom_vjp` over two Mosaic kernels, named so the
device trace carries them: `gdr_fwd` (the forward pass, and its
rematerialised copy) and `gdr_bwd`. Both have the grid (sequence, KEY
head, chunk), laid out as `ops/ssd.py` is: a grid step holds the H / G
value heads that share one q and k — so K K^T and Q K^T are formed once
for them, and the gradients of q and k are summed over them before they
leave — and walks them one by one (their inverses two by two); the
chunks are the innermost axis, walked in order, the heads' states `[H /
G, K, V]` float32 in VMEM scratch. The backward walks the chunks in
REVERSE with the state's gradient in that scratch. It reads each chunk's
entering state, which the forward SAVES when it is differentiated: `[B,
T / C, H, K, V]` float32, 268 MB a sequence of 8192 at 32 heads of 128 x
128, written once and read once, alive between a block's rematerialised
forward and its backward only. A forward that is not differentiated
writes none.

`Tm`: A is strictly lower triangular, so nilpotent, and `(I + A)^-1 =
(I - A)(I + A^2)(I + A^4) ..` ends after log2(C) factors: at C = 64 five
squarings and five products of `[64, 64]` (`inverse_products`), float32
at precision "highest" (`_inverse`) — six passes of the MXU each, whose
time goes with the rows a product streams whatever part of the 128 x
128 array its factors fill. So a key head's value heads go two by two
side by side in the lanes (`_inverses`, `paired_heads`: while two chunks
fit the 128 lanes and two heads are left; an odd head out goes alone):
`[x_a | x_b]` against blockdiag(x_a, x_b), whose zeros add exact zeros
to every sum, is both heads' product in the rows of one — ten products
of `[64, 128] [128, 128]` a pair where twenty of `[64, 64]` ran. (`inv
x'` and `x' x'` share their right-hand factor and could be one product
of twice the rows: it streams the same rows and measured slower, PERF.md
section 6, PR 58.) Tm's gradient needs no product with dTm: from `V' =
Tm R`, `dR = Tm^T dV'` and `dA = -Tm^T (dV' R^T) Tm^T = -dR V'^T`.

The running sums are made OUTSIDE the kernels, in XLA (a float32
`cumsum` over a chunk, differentiated by it), and come in twice, as
rows `[.., H / G, C]` and as columns `[.., C, H / G]`, beta as columns:
a chunk of 64 is half the 128 lanes, so a block `[H / G, C]` has to be
the two minor dimensions of its array whole, and the kernel transposes
nothing. The backward returns the sums' gradient in both layouts, each
taking the terms that fall out as rows or as columns. Exponents are
taken of masked DIFFERENCES: above the diagonal the difference is
replaced by -inf before the exponential, so a chunk that forgets
everything (`y_C` below about -87; at A up to 16 it reaches -1000) gives
zeros, not NaNs, and `e^{-y_j}` alone is never formed. The products run
in the inputs' dtype with float32 accumulation; the sums, every
exponential, Tm and the state are float32. The kernels are independent
over the batch: under a sharded jit each device runs them on its own
rows (`ops/partition.py`). `T % chunk != 0` raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private.accelerator import is_tpu
from ray_tpu.ops.partition import over_leading_dim

CHUNK = 64
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)
_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST
_LANES = 128


def inverse_products(chunk: int) -> int:
    """The `[C, C]` products the doubling names: a squaring and a
    product a factor after the first (whatever array they share)."""
    return 2 * max((chunk - 1).bit_length() - 1, 0)


def paired_heads(heads: int, chunk: int) -> int:
    """Of a key head's `heads` value heads, those whose inverses run two
    to a product: heads are paired while two chunks fit the lanes and
    there are two left to pair."""
    return heads - heads % 2 if 2 * chunk <= _LANES else 0


def _check(q, k, v, g, beta, chunk: int):
    batch, t, heads, _ = v.shape
    if t % chunk:
        raise ValueError(f"the delta rule walks whole chunks: T = {t} is "
                         f"not a multiple of {chunk}")
    if q.shape != k.shape or q.shape[:2] != (batch, t) \
            or heads % q.shape[2] or g.shape != (batch, t, heads) \
            or beta.shape != g.shape:
        raise ValueError(
            f"gated_delta: q {q.shape}, k {k.shape}, v {v.shape}, g "
            f"{g.shape}, beta {beta.shape} are not [B, T, G, K] twice, "
            "[B, T, H, V] and [B, T, H] twice with H a multiple of G")


def gated_delta_xla(q, k, v, g, beta, chunk: int = CHUNK,
                    initial_state=None):
    """The chunked equations (the WY form) in plain `jnp`, float32
    throughout -> o [B, T, H, V] in v's dtype. `initial_state`: the
    state `[B, H, K, V]` that enters the first chunk (default: zeros)."""
    _check(q, k, v, g, beta, chunk)
    batch, t, h, dv = v.shape
    groups, dk = q.shape[2:]
    nc, f32 = t // chunk, functools.partial(jnp.asarray, dtype=_F32)
    qc, kc = (jnp.repeat(f32(z).reshape(batch, nc, chunk, groups, dk),
                         h // groups, axis=3) for z in (q, k))
    vc = f32(v).reshape(batch, nc, chunk, h, dv)
    bc = f32(beta).reshape(batch, nc, chunk, h)
    y = jnp.cumsum(f32(g).reshape(batch, nc, chunk, h), axis=2)
    total = y[:, :, -1]                                   # [B, nc, H]
    diff = y[:, :, :, None] - y[:, :, None, :]            # [B, nc, i, j, H]
    at = jnp.arange(chunk)
    tri = (at[:, None] >= at[None, :])[None, None, :, :, None]
    strict = (at[:, None] > at[None, :])[None, None, :, :, None]
    decay = jnp.exp(jnp.where(tri, diff, -jnp.inf))
    kk = jnp.einsum("bcihd,bcjhd->bcijh", kc, kc)
    a = jnp.where(strict, bc[:, :, :, None] * kk * decay, 0.0)
    # W and U of every chunk: one unit-lower-triangular solve a chunk
    # and head, [e^y o K | V] o beta on the right
    rhs = bc[..., None] * jnp.concatenate(
        [jnp.exp(y)[..., None] * kc, vc], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        jnp.moveaxis(a, 4, 2) + jnp.eye(chunk), jnp.moveaxis(rhs, 3, 2),
        lower=True, unit_diagonal=True)                   # [B, nc, H, C, .]
    w, u = solved[..., :dk], solved[..., dk:]
    scores = jnp.moveaxis(
        jnp.einsum("bcihd,bcjhd->bcijh", qc, kc) * decay, 4, 2)
    k_out = jnp.moveaxis(jnp.exp(total[:, :, None] - y)[..., None] * kc, 3, 2)
    q_in = jnp.moveaxis(jnp.exp(y)[..., None] * qc, 3, 2)

    def step(state, part):
        w, u, scores, k_out, q_in, keep = part
        fresh = u - jnp.einsum("bhik,bhkv->bhiv", w, state)
        o = jnp.einsum("bhik,bhkv->bhiv", q_in, state) \
            + jnp.einsum("bhij,bhjv->bhiv", scores, fresh)
        state = keep[..., None, None] * state \
            + jnp.einsum("bhik,bhiv->bhkv", k_out, fresh)
        return state, o

    if initial_state is None:
        initial_state = jnp.zeros((batch, h, dk, dv), _F32)
    _, o = lax.scan(step, f32(initial_state), tuple(
        z.swapaxes(0, 1) for z in (w, u, scores, k_out, q_in,
                                   jnp.exp(total))))
    # [nc, B, H, C, V] -> [B, T, H, V]
    return o.transpose(1, 0, 3, 2, 4).reshape(batch, t, h, dv).astype(
        v.dtype)


def _dot(x, y, dims, precision=None):
    return lax.dot_general(x, y, (dims, ((), ())), precision=precision,
                           preferred_element_type=_F32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _inverse(a, eye):
    """(I + a)^-1 of a strictly lower triangular a [C, C], float32:
    with x = -a, (I + x)(I + x^2)(I + x^4) .. = the sum of x's powers,
    which ends at x^(C - 1). `a` [C, 2 C] is two heads' side by side in
    the lanes (`eye` stays [C, C]): the factor on the right is then
    blockdiag(x_a, x_b) — x stacked on itself in the rows, under a mask
    — whose zeros add exact zeros to every sum."""
    c, width = a.shape
    if width == c:
        def right(x):
            return x
    else:
        eye = jnp.concatenate([eye, eye], axis=1)
        row = lax.broadcasted_iota(jnp.int32, (2 * c, width), 0)
        col = lax.broadcasted_iota(jnp.int32, (2 * c, width), 1)
        same_head = (row < c) == (col < c)

        def right(x):
            return jnp.where(same_head, jnp.concatenate([x, x], axis=0), 0.0)

    x = -a
    inv, factor = eye + x, right(x)
    for _ in range(inverse_products(c) // 2):
        x = _dot(x, factor, _NN, _HIGHEST)
        factor = right(x)
        inv = inv + _dot(inv, factor, _NN, _HIGHEST)
    return inv


def _inverses(a, eye):
    """Tm of each head of a grid step (`a`: the heads' A, [C, C] each):
    the heads `paired_heads` pairs two to a product, the rest alone."""
    c = eye.shape[0]
    paired = paired_heads(len(a), c)
    out = []
    for j in range(0, paired, 2):
        both = _inverse(jnp.concatenate(a[j:j + 2], axis=1), eye)
        out += [both[:, :c], both[:, c:]]
    return out + [_inverse(x, eye) for x in a[paired:]]


def _chunk_parts(q_ref, k_ref, yr_ref, v_width: int):
    """What every head of a grid step shares: q, k, K K^T, Q K^T, the
    masks i >= j and i > j, the identity, and exp(y_C) [heads, V], a
    head's lanes alike (a [1, 1] value cannot be broadcast along
    sublanes and lanes at once: the product with a selector of the last
    position lays it along the lanes)."""
    c = q_ref.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    q, k = q_ref[...], k_ref[...]
    last = (lax.broadcasted_iota(jnp.int32, (c, v_width), 0)
            == c - 1).astype(_F32)
    keep = jnp.exp(_dot(yr_ref[...], last, _NN, _HIGHEST))
    return q, k, _dot(k, k, _NT), _dot(q, k, _NT), row >= col, row > col, \
        (row == col).astype(_F32), keep


def _head_parts(j: int, yr_ref, yc_ref, bc_ref, kk, tri, strict):
    """Head j's decay mask D [C, C] (zero above the diagonal), the same
    strictly below it, A, beta, exp(y) and exp(y_C - y) as columns,
    exp(y_C) [1, 1]."""
    c = tri.shape[0]
    yr, yc = yr_ref[j:j + 1, :], yc_ref[:, j:j + 1]
    beta = bc_ref[:, j:j + 1]
    total = yr[:, c - 1:c]
    decay = jnp.exp(jnp.where(tri, yc - yr, -jnp.inf))
    lower = jnp.where(strict, decay, 0.0)
    return decay, lower, beta * kk * lower, beta, jnp.exp(yc), \
        jnp.exp(total - yc), jnp.exp(total)


def _fwd_kernel(q_ref, k_ref, v_ref, yr_ref, yc_ref, bc_ref, o_ref, *rest,
                dv: int):
    state = rest[-1]                   # scratch [heads, K, V] float32
    entering = rest[0] if len(rest) == 2 else None

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q, k, kk, qk, tri, strict, eye, keep = _chunk_parts(
        q_ref, k_ref, yr_ref, dv)
    dtype = k.dtype
    cast = functools.partial(jnp.asarray, dtype=dtype)
    parts = [_head_parts(j, yr_ref, yc_ref, bc_ref, kk, tri, strict)
             for j in range(state.shape[0])]
    for j, inv in enumerate(_inverses([p[2] for p in parts], eye)):
        lanes = pl.ds(j * dv, dv)
        decay, _, _, beta, e_y, w, _ = parts[j]
        s = state[j]
        if entering is not None:
            entering[j] = s
        sc = cast(s)
        rhs = beta * (v_ref[:, lanes].astype(_F32) - e_y * _dot(k, sc, _NN))
        fresh = cast(_dot(cast(inv), cast(rhs), _NN))
        o = e_y * _dot(q, sc, _NN) + _dot(cast(qk * decay), fresh, _NN)
        o_ref[:, lanes] = o.astype(o_ref.dtype)
        state[j] = keep[j:j + 1, :] * s + _dot(
            cast(w * k.astype(_F32)), fresh, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, yr_ref, yc_ref, bc_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dyr_ref, dyc_ref, dbc_ref, dstate,
                *, dv: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    q, k, kk, qk, tri, strict, eye, keep = _chunk_parts(
        q_ref, k_ref, yr_ref, dv)
    dtype, (heads, c) = k.dtype, yr_ref.shape
    cast = functools.partial(jnp.asarray, dtype=dtype)
    kf = k.astype(_F32)
    dq, dk = jnp.zeros(q.shape, _F32), jnp.zeros(k.shape, _F32)
    dkk, dqk = jnp.zeros((c, c), _F32), jnp.zeros((c, c), _F32)
    dy_r, dy_c, dbeta_c = (jnp.zeros(shape, _F32) for shape in
                           ((heads, c), (c, heads), (c, heads)))
    at_row = lax.broadcasted_iota(jnp.int32, (heads, c), 0)
    at_col = lax.broadcasted_iota(jnp.int32, (c, heads), 1)
    last = lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    parts = [_head_parts(j, yr_ref, yc_ref, bc_ref, kk, tri, strict)
             for j in range(heads)]
    for j, inv in enumerate(_inverses([p[2] for p in parts], eye)):
        lanes = pl.ds(j * dv, dv)
        decay, lower, a, beta, e_y, w, e_total = parts[j]
        s, ds = s_ref[j], dstate[j]
        sc, dsc = cast(s), cast(ds)
        # the forward's own values again
        k_s, q_s = _dot(k, sc, _NN), _dot(q, sc, _NN)
        inner = v_ref[:, lanes].astype(_F32) - e_y * k_s
        fresh = _dot(cast(inv), cast(beta * inner), _NN)     # V' [C, V]
        p = qk * decay
        do = do_ref[:, lanes].astype(_F32)
        # V' = Tm R feeds the output and the next state
        dfresh = _dot(cast(p), cast(do), _TN) \
            + _dot(cast(w * kf), dsc, _NN)
        dp = _dot(cast(do), cast(fresh), _NT)                # dO V'^T
        dr = _dot(cast(inv), cast(dfresh), _TN)              # Tm^T dV'
        da = -_dot(cast(dr), cast(fresh), _NT)               # -dR V'^T
        dinner = beta * dr
        dk_s = -e_y * dinner
        e_do = e_y * do
        dv_ref[:, lanes] = dinner.astype(dv_ref.dtype)
        dq += _dot(cast(e_do), sc, _NT)
        dk += _dot(cast(dk_s), sc, _NT)
        into_next = _dot(cast(fresh), dsc, _NT)              # d(w o K)
        dk += w * into_next
        dw = (kf * into_next).sum(1, keepdims=True)          # [C, 1]
        dkk += da * beta * lower
        dqk += dp * decay
        dstate[j] = keep[j:j + 1, :] * ds + _dot(q, cast(e_do), _TN) \
            + _dot(k, cast(dk_s), _TN)
        # y_i - y_j under the masks: +row sums, -column sums of dD o D;
        # exp(y) on the state's parts; exp(y_C - y) on what enters the
        # next state, whose y_C (with exp(y_C) S's) is position C - 1
        moved = da * a + dp * p
        d_total = (dw * w).sum(0, keepdims=True) + e_total * (
            s * ds).sum(1, keepdims=True).sum(0, keepdims=True)
        d_e_y = (do * q_s).sum(1, keepdims=True) \
            - (dinner * k_s).sum(1, keepdims=True)
        dy_c = jnp.where(
            at_col == j,
            moved.sum(1, keepdims=True) + d_e_y * e_y - dw * w, dy_c)
        dy_r = jnp.where(
            at_row == j, jnp.where(last, d_total, 0.0)
            - moved.sum(0, keepdims=True), dy_r)
        dbeta_c = jnp.where(
            at_col == j, (da * kk * lower).sum(1, keepdims=True)
            + (dr * inner).sum(1, keepdims=True), dbeta_c)
    # K K^T and Q K^T are the heads' alike: their gradients' products once
    dq += _dot(cast(dqk), k, _NN)
    dk += _dot(cast(dqk), q, _TN) + _dot(cast(dkk), k, _NN) \
        + _dot(cast(dkk), k, _TN)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dyr_ref[...] = dy_r
    dyc_ref[...] = dy_c
    dbc_ref[...] = dbeta_c


def _specs(chunk: int, heads: int, dk: int, dv: int, at):
    """Block specs of one grid step's [C, K] rows of q (k, dq, dk), its
    [C, heads * V] of v (o, do, dv), the sums as rows [heads, C] and as
    columns [C, heads] (beta), and the [heads, K, V] states, the chunk
    index given by `at(c)`."""
    return {
        "q": pl.BlockSpec((None, chunk, dk), lambda i, g, c: (i, at(c), g)),
        "v": pl.BlockSpec((None, chunk, heads * dv),
                          lambda i, g, c: (i, at(c), g)),
        "rows": pl.BlockSpec((None, None, None, heads, chunk),
                             lambda i, g, c: (i, at(c), g, 0, 0)),
        "cols": pl.BlockSpec((None, None, None, chunk, heads),
                             lambda i, g, c: (i, at(c), g, 0, 0)),
        "state": pl.BlockSpec((None, None, heads, dk, dv),
                              lambda i, g, c: (i, at(c), g, 0, 0))}


def _sizes(q, v, y_r):
    batch, nc, groups, heads, chunk = y_r.shape
    return batch, nc, groups, heads, chunk, q.shape[2] // groups, \
        v.shape[2] // (groups * heads)


def _fwd_call(q, k, v, y_r, y_c, beta_c, *, save: bool):
    batch, nc, groups, heads, chunk, dk, dv = _sizes(q, v, y_r)
    s = _specs(chunk, heads, dk, dv, lambda c: c)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [s["v"]]
    if save:
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, nc, groups * heads, dk, dv), _F32))
        out_specs.append(s["state"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, dv=dv),
        grid=(batch, groups, nc),
        in_specs=[s["q"], s["q"], s["v"], s["rows"], s["cols"], s["cols"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        compiler_params=_PARAMS, interpret=not is_tpu(), name="gdr_fwd",
    )(q, k, v, y_r, y_c, beta_c)
    return tuple(out) if save else out[0]


def _bwd_call(q, k, v, y_r, y_c, beta_c, entering, do):
    batch, nc, groups, heads, chunk, dk, dv = _sizes(q, v, y_r)
    s = _specs(chunk, heads, dk, dv, lambda c: nc - 1 - c)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dv=dv),
        grid=(batch, groups, nc),
        in_specs=[s["q"], s["q"], s["v"], s["rows"], s["cols"], s["cols"],
                  s["state"], s["v"]],
        out_specs=[s["q"], s["q"], s["v"], s["rows"], s["cols"], s["cols"]],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(y_r.shape, _F32),
            jax.ShapeDtypeStruct(y_c.shape, _F32),
            jax.ShapeDtypeStruct(beta_c.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        compiler_params=_PARAMS, interpret=not is_tpu(), name="gdr_bwd",
    )(q, k, v, y_r, y_c, beta_c, entering, do)


_SPLIT = (True,) * 6


@jax.custom_vjp
def _rule(q, k, v, y_r, y_c, beta_c):
    """The kernels' own layout: q, k [B, T, G * K]; v [B, T, H * V]; the
    running sums of g as rows y_r [B, T / C, G, H / G, C] and as columns
    y_c [B, T / C, G, C, H / G], beta_c as columns, float32 -> o like
    v."""
    return over_leading_dim(functools.partial(_fwd_call, save=False),
                            _SPLIT)(q, k, v, y_r, y_c, beta_c)


def _rule_fwd(q, k, v, y_r, y_c, beta_c):
    o, entering = over_leading_dim(functools.partial(_fwd_call, save=True),
                                   _SPLIT)(q, k, v, y_r, y_c, beta_c)
    return o, (q, k, v, y_r, y_c, beta_c, entering)


def _rule_bwd(res, do):
    return over_leading_dim(_bwd_call, _SPLIT + (True, True))(*res, do)


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta(q, k, v, g, beta, chunk: int = CHUNK):
    """q, k: [B, T, G, K], normalised and scaled; v: [B, T, H, V]; g
    (the log decay, <= 0) and beta: [B, T, H] float32 -> o [B, T, H, V]
    in v's dtype, from a zero state. The layouts the kernels read — a
    chunk's running sums of g as rows and as columns, beta as columns,
    a key head's value heads together — are made here, in XLA, and
    differentiated by it."""
    _check(q, k, v, g, beta, chunk)
    batch, t, h, dv = v.shape
    groups, dk = q.shape[2:]
    nc, heads = t // chunk, h // groups

    def chunked(x):             # [B, T, H] -> [B, nc, G, C, H / G]
        return x.astype(_F32).reshape(batch, nc, chunk, groups, heads) \
            .swapaxes(2, 3)

    y_c = jnp.cumsum(chunked(g), axis=3)
    o = _rule(q.reshape(batch, t, groups * dk),
              k.reshape(batch, t, groups * dk), v.reshape(batch, t, h * dv),
              y_c.swapaxes(3, 4), y_c, chunked(beta))
    return o.reshape(batch, t, h, dv)
