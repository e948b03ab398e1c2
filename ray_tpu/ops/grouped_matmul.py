"""The routed expert layer's grouped matmul, walked BY GROUP.

``lhs`` holds rows sorted by group, ``rhs`` [G, K, N] one matrix a group,
``sizes`` [G] the rows of each: group g's rows times ``rhs[g]``. The grid
walks the groups that HAVE rows, one grid step each: the group's matrix
comes through the pipeline (the next group's is fetched under this one's
products), its rows are copied in by the kernel itself, a chunk of at most
``c`` at a time, and the results copied out, ONLY the rows the group has.
So every touched group's matrix is fetched once and passes through the MXU
once a chunk (one chunk unless the group outgrows ``c``), and no row is
moved that is not a group's. (The megablox ``gmm`` walks (row tile, group)
pairs over rows packed densely: a group that crosses a tile's edge is a
second step that fetches nothing and pushes the whole matrix through the
MXU again, with the memory idle meanwhile: its pipeline looks ONE step
ahead. A grid over (group, row tile of that group) with tiles of fixed
size cures that and pays for it in dead rows: measured, PERF.md section 6,
"PR 49 and PR 50".) Copies start on multiples of ALIGN rows, so the rows
lie in an ALIGNED layout, inputs and outputs alike: :func:`aligned_order`.
A SwiGLU's gate and up products are ONE call (``rhs_up``): both matrices
of a group in a grid step, its rows read once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ALIGN = 16   # rows: a bf16 tile's; every copy starts and ends on it


def aligned_rows(m: int, n_groups: int) -> int:
    """Rows of the aligned layout of ``m`` rows over ``n_groups``: each
    group that has a row starts on a multiple of ALIGN, so it wastes at
    most ALIGN - 1."""
    return -(-(m + min(n_groups, m) * (ALIGN - 1)) // ALIGN) * ALIGN


def chunk_rows(a: int) -> int:
    """Rows of one product inside the kernel for a call of ``a`` aligned
    rows: 128 (the MXU's), or the largest power of two a smaller call
    holds."""
    c = ALIGN
    while 2 * c <= min(a, 128):
        c *= 2
    return c


def aligned_order(key, sizes):
    """The ALIGNED layout of rows that belong to groups: group g's rows
    lie from ``sum(ceil(sizes[:g] / ALIGN) * ALIGN)`` on, in their own
    order. key [M]: the group of each row (a row of no group: G or more);
    sizes [G]: the rows of each group. Returns (take [A], lie [M]), A =
    :func:`aligned_rows`: aligned row a holds row ``take[a]`` (M or more:
    padding), row p lies at aligned row ``lie[p]``. ONE sort lays the rows
    out: the padding rides in it as rows of its own, behind its group's."""
    m, g = key.shape[0], sizes.shape[0]
    spare = jnp.arange(aligned_rows(m, g) - m, dtype=jnp.int32)
    pad_ends = jnp.cumsum(-sizes % ALIGN)
    # spare row d pads the group whose padding it falls in; what is left
    # over sorts behind every group (compares down the MAJOR axis and a
    # sum: a few vector ops, where a gather of G entries would crawl)
    pads = jnp.sum(pad_ends[:, None] <= spare[None, :], axis=0,
                   dtype=key.dtype)
    take = jnp.argsort(jnp.concatenate([key, pads]), stable=True)
    return take, jnp.argsort(take)[:m]


def walk_of(sizes):
    """What the grid walks: (walk [3, G] int32, n). Grid step v < n is
    group ``walk[0, v]``, the v-th that has rows, whose ``walk[2, v]``
    rows start at aligned row ``walk[1, v] * ALIGN``."""
    g = sizes.shape[0]
    a_sizes = -(-sizes // ALIGN) * ALIGN
    has = sizes > 0
    nth = jnp.cumsum(has) - has                  # a group's place in the walk
    is_v = (nth[:, None] == jnp.arange(g)[None, :]) & has[:, None]  # [G, V]
    walk = jnp.stack([
        jnp.sum(jnp.where(is_v, x[:, None], 0), axis=0, dtype=jnp.int32)
        for x in (jnp.arange(g), (jnp.cumsum(a_sizes) - a_sizes) // ALIGN,
                  sizes)])
    return walk, jnp.sum(has, dtype=jnp.int32)


def walk_fits(walk, n, a: int):
    """Whether a :func:`walk_of` of ``n`` steps stays inside ``a`` aligned
    rows: its last group ends there (``a`` is a multiple of ALIGN, so the
    group's padding fits where its rows do)."""
    last = jnp.maximum(n - 1, 0)
    return walk[1, last] * ALIGN + walk[2, last] <= a


def _kernel(walk, lhs, rhs, *rest, c, tn):
    *rhs_up, out, lbuf, obuf, sems = rest       # rhs_up: one matrix or none
    v = pl.program_id(1)
    cols = pl.ds(pl.multiple_of(pl.program_id(0) * tn, tn), tn)
    first, n = walk[1, v] * ALIGN, walk[2, v]

    def move(i, ends, sem):
        """Chunk i's rows (rounded up to ALIGN) between the aligned layout
        and a chunk's buffer, as copies of c, c/2, .. ALIGN rows, one for
        each binary digit of their count, all in flight together.
        ``ends``: (the rows in the layout, the rows in the buffer) -> the
        copy's (source, destination)."""
        rows = jnp.minimum(n - i * c, c)
        rows = (rows + ALIGN - 1) // ALIGN * ALIGN
        done, size, found = 0, c, []
        while size >= ALIGN:
            there = (rows & size) != 0
            at = pl.ds(pl.multiple_of(first + i * c + done, ALIGN), size)
            here = pl.ds(pl.multiple_of(done, ALIGN), size)
            found.append((there, pltpu.make_async_copy(*ends(at, here), sem)))
            done = done + jnp.where(there, size, 0)
            size //= 2
        for wait in (False, True):
            for there, copy in found:
                @pl.when(there)
                def _():
                    copy.wait() if wait else copy.start()

    def chunk(i, _):
        move(i, lambda at, here: (lhs.at[at], lbuf.at[here]), sems.at[0])
        x = lbuf[...]
        y = jnp.dot(x, rhs[...], preferred_element_type=jnp.float32)
        if rhs_up:
            # a SwiGLU's first half: ``rhs`` is the gate's matrix; both
            # products are rounded as calls of their own would round them,
            # the activation is float32 and rounded once
            up = jnp.dot(x, rhs_up[0][...],
                         preferred_element_type=jnp.float32)
            y = jax.nn.silu(y.astype(obuf.dtype).astype(jnp.float32)) \
                * up.astype(obuf.dtype).astype(jnp.float32)
        obuf[...] = y.astype(obuf.dtype)
        move(i, lambda at, here: (obuf.at[here], out.at[at, cols]), sems.at[1])

    jax.lax.fori_loop(0, (n + c - 1) // c, chunk, None)


def grouped_matmul(lhs, rhs, walk, n, *, c: int, tn: int, out_dtype,
                   rhs_up=None, interpret: bool = False):
    """One product over a :func:`walk_of`: lhs [A, K] in the aligned layout
    (:func:`aligned_order`), rhs [G, K, N] -> [A, N] ``out_dtype`` in the
    same layout, float32 accumulation over the whole of K, ``tn`` columns
    a grid step. Rows of no group come out as whatever memory held. With
    ``rhs_up`` [G, K, N] the call is a SwiGLU's first half, both matrices
    of a group in one grid step and its rows read once: ``silu(lhs x rhs) *
    (lhs x rhs_up)``, each product rounded to ``out_dtype`` first.

    PRECONDITION: the rows ARE in the aligned layout of the walk's sizes.
    The kernel's copies are not bounds-checked, so a walk that ends past
    row A (rows packed densely, as the megablox ``gmm`` took them, with
    sizes whose aligned layout is longer) would read and WRITE past the
    buffers: such a call walks NO group, and every row comes out as
    whatever memory held (:func:`walk_fits`)."""
    a, k = lhs.shape
    n_cols = rhs.shape[2]
    if n_cols % tn or a % ALIGN or c % ALIGN or c & (c - 1):
        raise ValueError(f"chunk {c} / columns {tn} do not fit lhs "
                         f"{lhs.shape} x rhs {rhs.shape}")
    n = jnp.where(walk_fits(walk, n, a), n, 0)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    matrix = pl.BlockSpec((None, k, tn),
                          lambda n_i, v, walk: (walk[0, v], 0, n_i))
    matrices = (rhs,) if rhs_up is None else (rhs, rhs_up)
    itemsize = jnp.dtype(out_dtype).itemsize
    vmem = (len(matrices) * 2 * k * tn * rhs.dtype.itemsize
            + c * k * lhs.dtype.itemsize
            + c * tn * (itemsize + 4 * (len(matrices) + 2)))
    return pl.pallas_call(
        functools.partial(_kernel, c=c, tn=tn),
        out_shape=jax.ShapeDtypeStruct((a, n_cols), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[any_space] + [matrix] * len(matrices),
            out_specs=any_space,
            grid=(n_cols // tn, n),
            scratch_shapes=[pltpu.VMEM((c, k), lhs.dtype),
                            pltpu.VMEM((c, tn), out_dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 * 1024 * 1024, 2 * vmem)),
        cost_estimate=pl.CostEstimate(
            flops=2 * a * k * n_cols * len(matrices),
            transcendentals=a * n_cols * (len(matrices) - 1),
            bytes_accessed=(len(matrices) * walk.shape[1] * k * n_cols
                            * rhs.dtype.itemsize
                            + a * k * lhs.dtype.itemsize * (n_cols // tn)
                            + a * n_cols * itemsize)),
        interpret=interpret, name="gmm",
    )(walk, lhs, *matrices)
