"""The Mamba-2 scan (state-space duality, arXiv:2405.21060), as one
operator.

Per head, with a scalar decay a position `a_t = exp(dt_t A)`, `A < 0`:

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        S: [P, N], float32
    y_t = S_t C_t + D x_t

`x: [B, T, H, P]`, `dt: [B, T, H]` (after its softplus, float32),
`A, D: [H]`, `B, C: [B, T, G, N]`; head h reads group `h // (H / G)`.
In chunks of Q positions, with `l_t` the running sum of `dt A` inside a
chunk and `S_in` the state that enters it:

    Y     = ((C B^T) o L)(dt o X) + exp(l) o (C S_in^T) + D o X
    L_ts  = exp(l_t - l_s) for s <= t, else 0
    S_out = exp(l_Q) S_in + (exp(l_Q - l) o dt o X)^T B

Two forms of the same function. `ssd_xla` is those equations in plain
`jnp` (a `lax.scan` carries the state over the chunks), differentiated
by JAX, and what the kernels are tested against. `ssd` has a
`custom_vjp` over two Mosaic kernels, named so the device trace carries
them: `ssd_fwd` (the forward pass, and its rematerialised copy) and
`ssd_bwd`. Both have the grid (sequence, GROUP of heads, chunk): a grid
step holds the H / G heads that share one B and C — so C B^T is formed
once for them, and the gradients of B and C are summed over them before
they leave — and walks them one by one; the chunks are the innermost
axis, walked in order, the heads' states `[H / G, P, N]` float32 in VMEM
scratch. The backward walks the chunks in REVERSE with the state's
gradient in that scratch (`dS_in = exp(l_Q) dS_out + (exp(l) o dY)^T C`,
the forward's recurrence run against time). It reads each chunk's
entering state, which the forward SAVES when it is differentiated:
`[B, T / Q, H, P, N]` float32, 134 MB a sequence of 8192 at 64 heads of
64 x 128, written once and read once, alive between a block's
rematerialised forward and its backward only; recomputing them instead
would be a third walk of the forward. A forward that is not
differentiated writes none.

`dt` comes to the kernels as rows, `[B, H, T]`: a chunk's running sum is
one float32 product with a triangle of ones (precision "highest": the
sums are exponentiated), and the column forms a row scaling needs are
transposes of an `[H / G, Q]` tile inside the kernel. The masked
exponent is taken of `min`-free differences: above the diagonal the
difference is replaced by -inf before the exponential, so a chunk that
forgets everything (`l_Q` below about -87) gives zeros, not NaNs. The
products run in the inputs' dtype with float32 accumulation; `dt`, `A`,
the sums, the state and every exponential are float32. The kernels are
independent over the batch: under a sharded jit each device runs them
on its own rows (`ops/partition.py`). `T % chunk != 0` raises.
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

CHUNK = 128
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)
_F32 = jnp.float32


def _check(x, dt, a, b, c, d, chunk: int):
    batch, t, h, _ = x.shape
    g = b.shape[2]
    if t % chunk:
        raise ValueError(f"the scan walks whole chunks: T = {t} is not a "
                         f"multiple of {chunk}")
    if h % g or dt.shape != (batch, t, h) or a.shape != (h,) \
            or d.shape != (h,) or b.shape != c.shape \
            or b.shape[:2] != (batch, t):
        raise ValueError(
            f"ssd: x {x.shape}, dt {dt.shape}, A {a.shape}, B {b.shape}, "
            f"C {c.shape}, D {d.shape} are not [B, T, H, P], [B, T, H], "
            "[H], [B, T, G, N] twice and [H] with H a multiple of G")


def ssd_xla(x, dt, a, b, c, d, chunk: int = CHUNK):
    """The chunked equations in plain `jnp`, float32 throughout ->
    y [B, T, H, P] in x's dtype."""
    _check(x, dt, a, b, c, d, chunk)
    batch, t, h, p = x.shape
    g, n = b.shape[2:]
    nc, f32 = t // chunk, functools.partial(jnp.asarray, dtype=_F32)
    xc = f32(x).reshape(batch, nc, chunk, h, p)
    dtc = f32(dt).reshape(batch, nc, chunk, h)
    bc, cc = (jnp.repeat(f32(z).reshape(batch, nc, chunk, g, n), h // g,
                         axis=3) for z in (b, c))
    ell = jnp.cumsum(dtc * f32(a), axis=2)                # [B, nc, Q, H]
    total = ell[:, :, -1]                                 # [B, nc, H]
    diff = ell[:, :, :, None] - ell[:, :, None, :]        # [B, nc, t, s, H]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(tri, diff, -jnp.inf))
    xs = dtc[..., None] * xc
    scores = jnp.einsum("bkthn,bkshn->bktsh", cc, bc) * decay
    y = jnp.einsum("bktsh,bkshp->bkthp", scores, xs)
    fresh = jnp.einsum(
        "bkthp,bkthn->bkhpn",
        jnp.exp(total[:, :, None] - ell)[..., None] * xs, bc)

    def step(state, part):
        keep, new = part
        return keep[..., None, None] * state + new, state

    _, entering = lax.scan(
        step, jnp.zeros((batch, h, p, n), _F32),
        (jnp.exp(total).swapaxes(0, 1), fresh.swapaxes(0, 1)))
    y = y + jnp.exp(ell)[..., None] * jnp.einsum(
        "bkthn,bkhpn->bkthp", cc, entering.swapaxes(0, 1))
    y = y + f32(d)[:, None] * xc
    return y.reshape(batch, t, h, p).astype(x.dtype)


def _dot(x, y, dims, precision=None):
    return lax.dot_general(x, y, (dims, ((), ())), precision=precision,
                           preferred_element_type=_F32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _chunk_parts(dt_ref, da_ref, b_ref, c_ref):
    """What every head of a grid step shares: the running sums as rows
    [heads, Q] and as columns [Q, heads], dt as columns, C B^T, and the
    mask s <= t, and exp(l_Q) [heads, N], a head's lanes alike (a [1, 1]
    value cannot be broadcast along sublanes and lanes at once: the
    product with ones lays it along the lanes)."""
    q, n = b_ref.shape
    row = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    ell_r = _dot(da_ref[...], (row <= col).astype(_F32), _NN,
                 lax.Precision.HIGHEST)
    keep = jnp.exp(_dot(da_ref[...], jnp.ones((q, n), _F32), _NN,
                        lax.Precision.HIGHEST))
    bm, cm = b_ref[...], c_ref[...]
    return ell_r, ell_r.T, dt_ref[...].T, bm, cm, _dot(cm, bm, _NT), \
        row >= col, keep


def _head_parts(j: int, ell_r, ell_c, tri):
    """Head j's decay mask L [Q, Q], exp(l) and exp(l_Q - l) as columns,
    exp(l_Q) [1, 1]."""
    q = tri.shape[0]
    lr, lc = ell_r[j:j + 1, :], ell_c[:, j:j + 1]
    total = lr[:, q - 1:q]
    decay = jnp.exp(jnp.where(tri, lc - lr, -jnp.inf))
    return decay, jnp.exp(lc), jnp.exp(total - lc), jnp.exp(total)


def _fwd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, d_ref, y_ref, *rest,
                p: int):
    state = rest[-1]                   # scratch [heads, P, N] float32
    entering = rest[0] if len(rest) == 2 else None

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    ell_r, ell_c, dt_c, bm, cm, scores, tri, keep = _chunk_parts(
        dt_ref, da_ref, b_ref, c_ref)
    dtype = bm.dtype
    for j in range(state.shape[0]):
        lanes = pl.ds(j * p, p)
        decay, e_c, w, e_q = _head_parts(j, ell_r, ell_c, tri)
        xj = x_ref[:, lanes].astype(_F32)
        xs = dt_c[:, j:j + 1] * xj
        s = state[j]
        if entering is not None:
            entering[j] = s
        y = _dot((scores * decay).astype(dtype), xs.astype(dtype), _NN) \
            + e_c * _dot(cm, s.astype(dtype), _NT) + d_ref[:, lanes] * xj
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        state[j] = keep[j:j + 1, :] * s + _dot((w * xs).astype(dtype), bm, _TN)


def _bwd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, d_ref, s_ref, dy_ref,
                dx_ref, ddt_ref, dda_ref, db_ref, dc_ref, dd_ref, dstate,
                *, p: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    ell_r, ell_c, dt_c, bm, cm, scores, tri, keep = _chunk_parts(
        dt_ref, da_ref, b_ref, c_ref)
    dtype, (heads, q) = bm.dtype, ell_r.shape
    cast = functools.partial(jnp.asarray, dtype=dtype)
    db, dc = jnp.zeros(bm.shape, _F32), jnp.zeros(cm.shape, _F32)
    # the gradients of the running sums and of dt, a head a row / column
    dl_r, dl_c, ddt_c = (jnp.zeros(shape, _F32) for shape in
                         ((heads, q), (q, heads), (q, heads)))
    at_row = lax.broadcasted_iota(jnp.int32, (heads, q), 0)
    at_col = lax.broadcasted_iota(jnp.int32, (q, heads), 1)
    last = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    for j in range(heads):
        lanes = pl.ds(j * p, p)
        decay, e_c, w, e_q = _head_parts(j, ell_r, ell_c, tri)
        m = scores * decay
        xj, dy = x_ref[:, lanes].astype(_F32), dy_ref[:, lanes].astype(_F32)
        xs = dt_c[:, j:j + 1] * xj
        s, ds = s_ref[j], dstate[j]
        b_ds = _dot(bm, cast(ds), _NT)                      # B dS^T [Q, P]
        dxs = _dot(cast(m), cast(dy), _TN) + w * b_ds
        dm = _dot(cast(dy), cast(xs), _NT)                  # dY Xs^T [Q, Q]
        dg = cast(dm * decay)
        c_s = e_c * _dot(cm, cast(s), _NT)                  # the state's part
        dc += _dot(dg, bm, _NN) + e_c * _dot(cast(dy), cast(s), _NN)
        db += _dot(dg, cm, _TN) + _dot(cast(w * xs), cast(ds), _NN)
        dstate[j] = keep[j:j + 1, :] * ds + _dot(cast(e_c * dy), cm, _TN)
        dvec = d_ref[:, lanes]
        dx_ref[:, lanes] = (dt_c[:, j:j + 1] * dxs
                            + dvec * dy).astype(dx_ref.dtype)
        dd_ref[:, lanes] = (dy * xj).sum(0, keepdims=True)
        # l_t - l_s under the mask: +row sums, -column sums of dM o M;
        # exp(l) on the state's part; exp(l_Q - l) on what enters the
        # next state, whose l_Q (with exp(l_Q) S_in's) is position Q - 1
        moved = dm * m
        through = (w * xs * b_ds).sum(1, keepdims=True)     # [Q, 1]
        d_total = through.sum(0, keepdims=True) \
            + e_q * (s * ds).sum(1, keepdims=True).sum(0, keepdims=True)
        dl_c = jnp.where(
            at_col == j, moved.sum(1, keepdims=True)
            + (dy * c_s).sum(1, keepdims=True) - through, dl_c)
        dl_r = jnp.where(
            at_row == j, jnp.where(last, d_total, 0.0)
            - moved.sum(0, keepdims=True), dl_r)
        ddt_c = jnp.where(at_col == j, (xj * dxs).sum(1, keepdims=True),
                          ddt_c)
    db_ref[...] = db.astype(db_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)
    ddt_ref[...] = ddt_c.T
    # l_t sums dt A over r <= t: its gradient sums dl over t >= r
    row = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    dda_ref[...] = _dot(dl_r + dl_c.T, (row >= col).astype(_F32), _NN,
                        lax.Precision.HIGHEST)


def _specs(chunk: int, heads: int, p: int, n: int, at):
    """Block specs of one grid step's [Q, heads * P] rows of x (y, dy,
    dx), its [heads, Q] rows of dt, its [Q, N] of B (C), D's lanes and
    the [heads, P, N] states, the chunk index given by `at(k)`."""
    return {
        "x": pl.BlockSpec((None, chunk, heads * p),
                          lambda i, g, k: (i, at(k), g)),
        "dt": pl.BlockSpec((None, heads, chunk),
                           lambda i, g, k: (i, g, at(k))),
        "b": pl.BlockSpec((None, chunk, n), lambda i, g, k: (i, at(k), g)),
        "d": pl.BlockSpec((1, heads * p), lambda i, g, k: (0, g)),
        "state": pl.BlockSpec((None, None, heads, p, n),
                              lambda i, g, k: (i, at(k), g, 0, 0)),
        "dd": pl.BlockSpec((None, None, 1, heads * p),
                           lambda i, g, k: (i, at(k), 0, g))}


def _sizes(x, dt_r, b, groups: int):
    batch, t, hp = x.shape
    h = dt_r.shape[1]
    return batch, t, h, hp // h, b.shape[2] // groups, h // groups


def _fwd_call(x, dt_r, da_r, b, c, dvec, *, chunk: int, groups: int,
              save: bool):
    batch, t, h, p, n, heads = _sizes(x, dt_r, b, groups)
    nc = t // chunk
    s = _specs(chunk, heads, p, n, lambda k: k)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [s["x"]]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((batch, nc, h, p, n), _F32))
        out_specs.append(s["state"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=(batch, groups, nc),
        in_specs=[s["x"], s["dt"], s["dt"], s["b"], s["b"], s["d"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, p, n), _F32)],
        compiler_params=_PARAMS, interpret=not is_tpu(), name="ssd_fwd",
    )(x, dt_r, da_r, b, c, dvec)
    return tuple(out) if save else out[0]


def _bwd_call(x, dt_r, da_r, b, c, dvec, entering, dy, *, chunk: int,
              groups: int):
    batch, t, h, p, n, heads = _sizes(x, dt_r, b, groups)
    nc = t // chunk
    s = _specs(chunk, heads, p, n, lambda k: nc - 1 - k)
    rows = jax.ShapeDtypeStruct(dt_r.shape, _F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=(batch, groups, nc),
        in_specs=[s["x"], s["dt"], s["dt"], s["b"], s["b"], s["d"],
                  s["state"], s["x"]],
        out_specs=[s["x"], s["dt"], s["dt"], s["b"], s["b"], s["dd"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype), rows, rows,
            jax.ShapeDtypeStruct(b.shape, b.dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype),
            jax.ShapeDtypeStruct((batch, nc, 1, h * p), _F32)],
        scratch_shapes=[pltpu.VMEM((heads, p, n), _F32)],
        compiler_params=_PARAMS, interpret=not is_tpu(), name="ssd_bwd",
    )(x, dt_r, da_r, b, c, dvec, entering, dy)


_SPLIT = (True, True, True, True, True, False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt_r, da_r, b, c, dvec, chunk: int, groups: int):
    """The kernels' own layout: x [B, T, H * P]; dt_r, da_r [B, H, T]
    float32 (dt and dt A, a head a row); b, c [B, T, G * N]; dvec
    [1, H * P] float32 (D, a head's P lanes alike) -> y like x."""
    return over_leading_dim(
        functools.partial(_fwd_call, chunk=chunk, groups=groups, save=False),
        _SPLIT)(x, dt_r, da_r, b, c, dvec)


def _scan_fwd(x, dt_r, da_r, b, c, dvec, chunk, groups):
    y, entering = over_leading_dim(
        functools.partial(_fwd_call, chunk=chunk, groups=groups, save=True),
        _SPLIT)(x, dt_r, da_r, b, c, dvec)
    return y, (x, dt_r, da_r, b, c, dvec, entering)


def _scan_bwd(chunk, groups, res, dy):
    dx, ddt, dda, db, dc, dd = over_leading_dim(
        functools.partial(_bwd_call, chunk=chunk, groups=groups),
        _SPLIT + (True, True))(*res, dy)
    return dx, ddt, dda, db, dc, dd.sum((0, 1))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd(x, dt, a, b, c, d, chunk: int = CHUNK):
    """x: [B, T, H, P]; dt: [B, T, H] float32, after its softplus; a,
    d: [H] float32 (A < 0); b, c: [B, T, G, N] in x's dtype -> y
    [B, T, H, P] in x's dtype. The layouts the kernels read — dt as
    rows, dt A, D a lane — are made here, in XLA, and differentiated by
    it: A's and D's gradients are sums over what the backward kernel
    returns."""
    _check(x, dt, a, b, c, d, chunk)
    batch, t, h, p = x.shape
    g, n = b.shape[2:]
    dt_r = dt.astype(_F32).swapaxes(1, 2)
    y = _scan(x.reshape(batch, t, h * p), dt_r,
              dt_r * a.astype(_F32)[None, :, None],
              b.reshape(batch, t, g * n), c.reshape(batch, t, g * n),
              jnp.repeat(d.astype(_F32), p)[None, :], chunk, g)
    return y.reshape(batch, t, h, p)
