"""The two operations of a state-space mixer whose state is a matrix a head
(Mamba-2): a sequence keeps ``S`` [H, N, P] float32 a layer, H heads of P
lanes against N state columns, and every token t does

    S_t[h] = a_t[h] * S_{t-1}[h] + dt_t[h] * B_t[g(h)] (outer) x_t[h]
    y_t[h] = C_t[g(h)] . S_t[h]          (a sum over the N state columns)

with ``a_t = exp(dt_t * A)``, ``A`` [H] negative, ``x_t`` [H, P], ``B_t`` /
``C_t`` [G, N] shared by the H / G heads of a group (head h reads group
``h // (H / G)``). The skip ``D * x``, the gate and the norm are the model's
(models/falcon_h1.py); ``dt`` arrives after its softplus, and a column
with ``dt = 0`` leaves the state as it was (``a = 1``, nothing added): how
a caller masks padding.

THE LAYOUT. A state is stored ``[H, N, P]``: P = 128 on the lanes, the N
state columns on the sublanes, so that the read-out ``C . S`` is a sum over
sublanes (adds of whole vectors) and its result a lane-dense row, and the
update's ``x`` is a row too. Only B and C have to stand as COLUMNS; the
kernel turns each group's row into one on the MXU (a product with ones,
exact). No weight depends on the layout; the plain reference keeps
``[H, P, N]``.

(a) :func:`chunk_scan`: a call's T columns from a carried state, in the
    state-space-dual form: inside a chunk of Q columns the outputs are
    products on the MXU (``(C B^T * L) x`` with ``L`` the decay between two
    columns), one state a chunk is passed on. Plain ``jax.numpy``: a
    prefill's scan is a few per cent of its mixer's two projections.
(b) :func:`decode_update`: ONE column a slot, in place on a pool of states
    ``[rows, H, N, P]``: a Pallas kernel with the slots' rows scalar-
    prefetched into the block index maps and the pool aliased input to
    output, so that a step moves a slot's state once in and once out.
    :func:`decode_update_xla` is the same in ``jax.numpy`` (gather, update,
    scatter): what the gather backend runs, and what the kernel is held to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import paged_attention as paged_ops

# heads of one grid step of the update kernel: a block of 8 x 256 x 128
# float32 is 1 MB, in and out and double-buffered 4 MB of VMEM
_HEADS_A_STEP = 8


def chunk_scan(x, dt, a, b, c, state, chunk: int):
    """T columns of ONE sequence from a carried state.

    x [T, H, P]; dt [T, H] float32 (0 = the column is padding); a [H]
    float32 (negative); b, c [T, G, N]; state [H, N, P] float32. Returns (y
    [T, H, P] float32, the state after the last column [H, N, P]). T is
    padded to whole chunks of ``chunk`` columns with ``dt = 0``."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    hg = h // g
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, b, c))
    nc = (t + pad) // chunk
    xs = x.reshape(nc, chunk, g, hg, p)
    dts = dt.astype(jnp.float32).reshape(nc, chunk, g, hg)
    bs, cs = b.reshape(nc, chunk, g, n), c.reshape(nc, chunk, g, n)
    a = a.astype(jnp.float32).reshape(g, hg)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    f32 = jnp.float32

    def one(s, inp):
        """A chunk: s [G, HG, N, P] the state before it."""
        xq, dtq, bq, cq = inp
        cum = jnp.cumsum(dtq * a, axis=0)                     # [Q, G, HG]
        # column i reads column j <= i through the decay between them
        seg = jnp.where(tri, cum[:, None] - cum[None, :], -jnp.inf)
        cb = jnp.einsum("ign,jgn->ijg", cq, bq, preferred_element_type=f32)
        m = cb[..., None] * jnp.exp(seg) * dtq[None]          # [Q, Q, G, HG]
        y = jnp.einsum("ijgh,jghp->ighp", m.astype(xq.dtype), xq,
                       preferred_element_type=f32)
        # ... and the state before the chunk through the decay since
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "ign,ghnp->ighp", cq.astype(f32), s)
        # the state after: what was, decayed over the chunk, and each column
        w = (jnp.exp(cum[-1][None] - cum) * dtq)[..., None] * xq.astype(f32)
        s = jnp.exp(cum[-1])[..., None, None] * s + jnp.einsum(
            "jgn,jghp->ghnp", bq.astype(f32), w)
        return s, y

    s, y = jax.lax.scan(one, state.astype(f32).reshape(g, hg, n, p),
                        (xs, dts, bs, cs))
    return y.reshape(nc * chunk, h, p)[:t], s.reshape(h, n, p)


def _operands(x, dt, a, b, c):
    """What an update reads beside the state, made once outside it: the
    decay ``exp(dt A)`` and ``dt x`` as rows a head, float32."""
    dt = dt.astype(jnp.float32)
    dec = jnp.exp(dt * a.astype(jnp.float32))                 # [B, H]
    dtx = dt[..., None] * x.astype(jnp.float32)               # [B, H, P]
    return dec, dtx, b.astype(jnp.float32), c.astype(jnp.float32)


def decode_update_xla(pool, rows, x, dt, a, b, c):
    """:func:`decode_update` in ``jax.numpy``: the rows gathered, updated
    and scattered back (three moves of a state where the kernel makes two;
    lanes that share a row, the trash row's, leave one of theirs)."""
    dec, dtx, b, c = _operands(x, dt, a, b, c)
    hg = pool.shape[1] // b.shape[1]
    bh, ch = (jnp.repeat(v, hg, axis=1) for v in (b, c))      # [B, H, N]
    s = dec[..., None, None] * pool[rows] \
        + bh[..., None] * dtx[:, :, None, :]
    y = jnp.sum(ch[..., None] * s, axis=2)
    return y, pool.at[rows].set(s)


def _update_kernel(rows_ref, dec_ref, dtx_ref, b_ref, c_ref, s_ref,
                   y_ref, out_ref):
    """One slot, ``_HEADS_A_STEP`` heads of one group. dec / dtx / y
    [hb, 1, P]; b / c [8, N] (row 0 real, zeros below); s / out [hb, N, P]."""
    del rows_ref
    ones = jnp.ones((8, s_ref.shape[-1]), jnp.float32)
    # a group's row as columns, the same on every lane: b^T ones on the MXU
    # (one term a product, so exact at any precision for values bf16 holds)
    col = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    bcol, ccol = col(b_ref[...], ones), col(c_ref[...], ones)   # [N, P]
    for h in range(s_ref.shape[0]):
        s = dec_ref[h] * s_ref[h] + bcol * dtx_ref[h]
        out_ref[h] = s
        y_ref[h] = jnp.sum(ccol * s, axis=0, keepdims=True)


def decode_update(pool, rows, x, dt, a, b, c, *, interpret=None):
    """One column a slot, in place on the pool.

    pool [R, H, N, P] float32; rows [B] int32, each slot's row (lanes with
    nothing to say meet in a trash row); x [B, H, P]; dt [B, H]; a [H];
    b, c [B, G, N]. Returns (y [B, H, P] float32, the pool): row
    ``rows[i]`` holds slot i's state after the column, every other row is
    untouched, and nothing but the named rows moves."""
    nb, h, p = x.shape
    g, n = b.shape[1:]
    hb = min(_HEADS_A_STEP, h // g)
    if (h // g) % hb or pool.shape[1:] != (h, n, p):
        raise ValueError(
            f"state pool {pool.shape} against {h} heads of {p} lanes, "
            f"{g} groups of {n} columns: a grid step takes {hb} heads of "
            f"one group")
    dec, dtx, b, c = _operands(x, dt, a, b, c)
    dec = jnp.broadcast_to(dec[..., None, None], (nb, h, 1, p))
    # rows of 8: a group's vector in row 0 of a whole float32 tile
    b8, c8 = (jnp.pad(v[:, :, None, :], ((0, 0), (0, 0), (0, 7), (0, 0)))
              for v in (b, c))
    steps = h // hb

    def head_block(i, j, rows_ref):
        return (i, j, 0, 0)

    def group_block(i, j, rows_ref):
        return (i, j * hb * g // h, 0, 0)

    def state_block(i, j, rows_ref):
        return (rows_ref[i], j, 0, 0)

    row = pl.BlockSpec((None, hb, 1, p), head_block)
    vec = pl.BlockSpec((None, None, 8, n), group_block)
    st = pl.BlockSpec((None, hb, n, p), state_block)
    y, pool = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nb, steps),
            in_specs=[row, row, vec, vec, st], out_specs=[row, st]),
        out_shape=[jax.ShapeDtypeStruct((nb, h, 1, p), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is written where it lies
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # a state block in and out, double-buffered, and the body's own
            vmem_limit_bytes=max(32 << 20, 6 * hb * n * p * 4)),
        # (interpreted wherever the paged kernels are: one rule)
        interpret=paged_ops.interpret_default() if interpret is None
        else interpret,
        name="ssm_decode_update",
    )(rows.astype(jnp.int32), dec, dtx[:, :, None, :], b8, c8, pool)
    return y[:, :, 0], pool
