"""Flash attention as two Pallas TPU kernels: `flash_fwd` and `flash_bwd`.

The attention of the transformer stack on the train path (`models/llama.py`
with `attn_impl="flash"`, and `models/moe.py` through it). Nothing
[T, T]-shaped reaches HBM: each kernel works on [block_q, block_k] tiles of
scores, streaming-softmax statistics in f32.

- Products run on the MXU with the operands as stored (bf16 x bf16 summed in
  f32 on the train path; f32 for the f32 tiny models of the tests).
- A head's K and V (and in the backward its Q and dO) stay whole in VMEM;
  the loops over blocks are inside the kernels. A causal call runs them over
  the blocks of the triangle only, and only the blocks the diagonal crosses
  take a mask.
- Grouped queries are served in the kernels: K and V come in at their own
  head count and are fetched once a KV head, query head h reads KV head
  h // n_rep, dk / dv sum over the n_rep query heads of a KV head in VMEM.
- The backward is one pass (dq, dk and dv from one set of scores), not
  FA2's two kernels.
- Block sizes come from the shapes (`default_blocks`), swept on a v5e.

`reference_attention` is XLA's own attention from two einsums, the fallback
off-TPU and the comparison of the tests; on CPU the kernels run in the Pallas
interpreter (`interpret=True`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
# dot_general dimension numbers: contract the last dims of both (q @ k^T,
# "NT"), the last of the left with the first of the right (p @ v, "NN"), the
# first of both ("TN"). The backward holds its scores TRANSPOSED, [k, q], so
# its two sums over q (dk, dv) are plain products; only dq = ds^T @ k has a
# transposed left operand.
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
# What a kernel may ask of VMEM: the v5e has 128 MiB, the compiler's own
# default scope is 16 MiB.
_VMEM_FLOOR = 32 * 1024 * 1024
_VMEM_CEILING = 100 * 1024 * 1024


def _dot(a, b, dims):
    """Operands as stored (bf16 on the train path, f32 in the tiny test
    models), products summed in f32."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _causal_bounds(lo, hi, step, num):
    """The blocks of `step` positions, of `num`, that [lo, hi) touches, as
    (first, one past the last): the blocks the diagonal crosses when
    [lo, hi) is the span of positions that some but not all rows of a tile
    may see."""
    return (jnp.minimum(lo // step, num),
            jnp.minimum((hi + step - 1) // step, num))


def _over_blocks(block, carry, *spans):
    """`block(i, carry, masked=...)` over each (first, past the last,
    masked) span of block indices in turn; the bounds may be traced."""
    for lo, hi, masked in spans:
        carry = jax.lax.fori_loop(
            lo, hi, functools.partial(block, masked=masked), carry)
    return carry


def _below_diagonal(block_q, block_k):
    """[block_q, block_k] int32: row - col. An element of the tile whose
    q rows start at q0 and k columns at k0 is visible iff this >= k0 - q0,
    so a diagonal tile pays one compare and one select against a scalar."""
    return (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))


def _lane_sums(p):
    """[rows, n] -> [rows, 128]: column j + 128 i summed over i, which
    is vreg adds only; the sum ACROSS lanes, which the VPU cannot do and
    the XLU does slowly, is left to whoever needs the row's total (once a
    q block, not once a tile). [rows, 1] where n is no multiple of 128."""
    n = p.shape[-1]
    if n % _LANES:
        return jnp.sum(p, axis=-1, keepdims=True)
    return sum(p[:, i:i + _LANES] for i in range(0, n, _LANES))


def _col_to_row(col, n):
    """[n, 1] f32 -> [1, n]: a per-row statistic from one value a sublane
    row to lane-dense (one value a lane), as lse is stored: the backward
    reads it as a row, and [T, 1] in HBM is padded to 128 lanes."""
    return jnp.transpose(jnp.broadcast_to(col, (n, _LANES)))[:1, :]


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale: float,
                      causal: bool, block_q: int, block_k: int, num_k: int):
    """One q block of one head against that head's whole K and V, which
    stay in VMEM across the q blocks and the n_rep query heads that share
    them. The loop runs over the k blocks the causal triangle needs and no
    others; max, sum and output ride it as [block_q, 1], [block_q, 128]
    (`_lane_sums`) and [block_q, D] values."""
    q = q_ref[0]
    q_start = pl.program_id(1) * block_q
    rel = _below_diagonal(block_q, block_k) if causal else None

    def block(c, carry, masked):
        m, l, acc = carry
        k_start = pl.multiple_of(c * block_k, block_k)
        k = k_ref[0, pl.ds(k_start, block_k), :]
        v = v_ref[0, pl.ds(k_start, block_k), :]
        s = _dot(q, k, _NT) * sm_scale                      # [bq, bk] f32
        if masked:
            s = jnp.where(rel >= k_start - q_start, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + _lane_sums(p)
        acc = acc * alpha + _dot(p.astype(v.dtype), v, _NN)
        return m_new, l, acc

    carry = (jnp.full((block_q, 1), _NEG_INF, jnp.float32),
             jnp.zeros((block_q, _LANES if block_k % _LANES == 0 else 1),
                       jnp.float32),
             jnp.zeros(q.shape, jnp.float32))
    if causal:
        # k blocks wholly at or before the block's first row, then the ones
        # the diagonal crosses (row r sees columns <= r)
        n_plain, n_all = _causal_bounds(q_start + 1, q_start + block_q,
                                        block_k, num_k)
        spans = ((0, n_plain, False), (n_plain, n_all, True))
    else:
        spans = ((0, num_k, False),)
    m, l, acc = _over_blocks(block, carry, *spans)
    l = jnp.sum(l, axis=-1, keepdims=True)
    o_ref[0] = (acc * (1.0 / l)).astype(o_ref.dtype)
    # log-sum-exp per row for the backward (FA2), stored lane-dense
    lse_ref[0] = _col_to_row(m + jnp.log(l), block_q)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                      sm_scale: float, causal: bool, block_q: int,
                      block_k: int, num_q: int, num_k: int):
    """The whole backward of one query head in one pass: every tile's
    scores, probabilities and ds are built once and feed dv, dk and dq:
    five products and one exponential a tile, where FA2's split into a dq
    and a dk / dv kernel takes seven and two (measured on the v5e: 1.57 ms
    against 2.26, PERF.md PR 34). Q, dO, K, V, lse and delta of the head
    are whole in VMEM, so dq accumulates in a [T, D] f32 scratch and not
    in HBM partials; dk / dv sum over the n_rep query heads of the KV head
    (the inner grid axis) in scratches written out after the last. The
    outer loop runs over k blocks, the inner over the q blocks at or past
    the diagonal; scores are [block_k, block_q], lse / delta lane-dense
    rows."""
    rep = pl.program_id(1)
    rel = _below_diagonal(block_k, block_q) if causal else None

    @pl.when(rep == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    dq_scr[...] = jnp.zeros_like(dq_scr)

    def k_block(ki, _):
        k_start = pl.multiple_of(ki * block_k, block_k)
        k = k_ref[0, pl.ds(k_start, block_k), :]
        v = v_ref[0, pl.ds(k_start, block_k), :]

        def q_block(i, carry, masked):
            dk, dv = carry
            q_start = pl.multiple_of(i * block_q, block_q)
            q = q_ref[0, pl.ds(q_start, block_q), :]
            g = g_ref[0, pl.ds(q_start, block_q), :]
            lse = lse_ref[0, :, pl.ds(q_start, block_q)]    # [1, bq]
            delta = delta_ref[0, :, pl.ds(q_start, block_q)]
            s = _dot(k, q, _NT) * sm_scale                  # [bk, bq]
            if masked:
                s = jnp.where(rel <= q_start - k_start, s, _NEG_INF)
            p = jnp.exp(s - lse)
            dv = dv + _dot(p.astype(g.dtype), g, _NN)
            dp = _dot(v, g, _NT)
            ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
            dq_scr[pl.ds(q_start, block_q), :] += _dot(ds, k, _TN)
            return dk + _dot(ds, q, _NN), dv

        if causal:
            # q blocks the diagonal crosses, then those wholly past the k
            # block's last row; q blocks before the k block give nothing
            first, n_crossed = _causal_bounds(
                k_start, k_start + block_k - 1, block_q, num_q)
            spans = ((first, n_crossed, True), (n_crossed, num_q, False))
        else:
            spans = ((0, num_q, False),)
        dk, dv = _over_blocks(
            q_block, (jnp.zeros(k.shape, jnp.float32),) * 2, *spans)
        dk_scr[pl.ds(k_start, block_k), :] += dk
        dv_scr[pl.ds(k_start, block_k), :] += dv
        return 0

    jax.lax.fori_loop(0, num_k, k_block, 0)
    dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when(rep == pl.num_programs(1) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _head_bytes(*arrays):
    """Bytes of one head's [T, D] slice of each [heads, T, D] array."""
    return sum(math.prod(x.shape[1:]) * x.dtype.itemsize for x in arrays)


def _params(semantics, resident_bytes, block_q, block_k):
    """The grid's semantics and the kernel's VMEM scope: what stays whole
    in VMEM plus room for a dozen f32 [block_q, block_k] tiles and the
    streamed blocks."""
    need = resident_bytes + 16 * block_q * block_k * 4
    if need > _VMEM_CEILING:
        raise ValueError(
            f"flash_attention: the sequence does not fit VMEM whole "
            f"({need >> 20} MiB of {_VMEM_CEILING >> 20}); split it over "
            f"chips (ring_attention)")
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=max(need, _VMEM_FLOOR))


def _flash_fwd_bh(q, k, v, *, causal, sm_scale, block_q, block_k, interpret):
    """q [B*H, T, D], k / v [B*Hkv, T, D] -> out [B*H, T, D], lse
    [B*H, 1, T] f32. Query head bh reads KV head bh // n_rep."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    n_rep = bh // k.shape[0]
    kv_spec = pl.BlockSpec((1, t_k, d), lambda b, qi: (b // n_rep, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_k=t_k // block_k),
        grid=(bh, t_q // block_q),
        in_specs=[pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
                  kv_spec, kv_spec],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda b, qi: (b, qi, 0)),
                   pl.BlockSpec((1, 1, block_q), lambda b, qi: (b, 0, qi))],
        out_shape=[jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, t_q), jnp.float32)],
        # K and V whole, twice for the pipeline's second buffer
        compiler_params=_params(("parallel", "arbitrary"),
                                2 * _head_bytes(k, v), block_q, block_k),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


def _flash_bwd_bh(q, k, v, g, lse, delta, *, causal, sm_scale, block_q,
                  block_k, interpret):
    """(dq [B*H, T, D], dk, dv [B*Hkv, T, D]) from q, g = dO [B*H, T, D],
    k, v [B*Hkv, T, D] and lse, delta [B*H, 1, T] f32."""
    bh, t_q, d = q.shape
    bkv, t_k, _ = k.shape
    n_rep = bh // bkv
    head = lambda b, r: (b * n_rep + r, 0, 0)
    kv_head = lambda b, r: (b, 0, 0)
    q_spec = pl.BlockSpec((1, t_q, d), head)
    kv_spec = pl.BlockSpec((1, t_k, d), kv_head)
    stat_spec = pl.BlockSpec((1, 1, t_q), head)
    return pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_q=t_q // block_q,
            num_k=t_k // block_k),
        grid=(bkv, n_rep),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((t_q, d), jnp.float32),
                        pltpu.VMEM((t_k, d), jnp.float32),
                        pltpu.VMEM((t_k, d), jnp.float32)],
        # inputs and outputs whole and double-buffered, the f32 scratches
        compiler_params=_params(
            ("parallel", "arbitrary"),
            2 * _head_bytes(q, k, v, g, q, k, v) + 4 * d * (t_q + 2 * t_k),
            block_q, block_k),
        interpret=interpret,
        name="flash_bwd",
    )(q, k, v, g, lse, delta)


def _to_bh(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bh(x, b):
    bh, t, d = x.shape
    return x.reshape(b, bh // b, t, d).transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, causal, sm_scale, blocks, interpret):
    from jax.ad_checkpoint import checkpoint_name
    bq, bk = blocks[0]
    out_bh, lse = _flash_fwd_bh(
        _to_bh(q), _to_bh(k), _to_bh(v), causal=causal, sm_scale=sm_scale,
        block_q=bq, block_k=bk, interpret=interpret)
    out = _from_bh(out_bh, q.shape[0])
    # "attn_lse" lets remat policies save the softmax stats so the backward
    # does not re-run the forward kernel just to rebuild them (the output
    # residual aliases the primal, which callers tag "attn").
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_core(q, k, v, causal, sm_scale, blocks, interpret):
    return _flash_fwd(q, k, v, causal, sm_scale, blocks, interpret)[0]


def _flash_bwd(causal, sm_scale, blocks, interpret, res, g):
    """delta = rowsum(dO * O) by XLA, the rest in `flash_bwd`."""
    q, k, v, out, lse = res
    b = q.shape[0]
    bq, bk = blocks[1]
    g_bh = _to_bh(g)
    delta = jnp.sum(g_bh.astype(jnp.float32) * _to_bh(out).astype(jnp.float32),
                    axis=-1)[:, None, :]                    # [BH, 1, Tq]
    dq, dk, dv = _flash_bwd_bh(
        _to_bh(q), _to_bh(k), _to_bh(v), g_bh, lse, delta, causal=causal,
        sm_scale=sm_scale, block_q=bq, block_k=bk, interpret=interpret)
    return _from_bh(dq, b), _from_bh(dk, b), _from_bh(dv, b)


_flash_attention_core.defvjp(_flash_fwd, _flash_bwd)

# Default (block_q, block_k) of `flash_fwd` and `flash_bwd` at head_dim
# 128, swept on a v5e at [64 heads, 2048, 128] bf16 causal over 256-2048
# (PERF.md, PR 34). A block is capped by its sequence; a wider head shrinks
# the k block in proportion (the tiles a kernel keeps live are block x D).
_BLOCK_TARGETS = ((512, 512), (512, 512))


def _pick_block(t: int, target: int) -> int:
    """The sequence itself when it is no longer than `target`, else the
    largest multiple of 128 (a lane tile: lse and delta are stored one
    row a lane) up to `target` that divides it."""
    if t <= target:
        return t
    for blk in range(target - target % _LANES, 0, -_LANES):
        if t % blk == 0:
            return blk
    raise ValueError(
        f"flash_attention: a sequence of {t} longer than one block of "
        f"{target} must be a multiple of {_LANES}; pad it")


def default_blocks(t_q: int, t_k: int, head_dim: int) -> tuple:
    """((block_q, block_k) of flash_fwd, (block_q, block_k) of flash_bwd)
    from the shapes."""
    shrink = max(1, head_dim // _LANES)
    return tuple((_pick_block(t_q, bq), _pick_block(t_k, bk // shrink))
                 for bq, bk in _BLOCK_TARGETS)


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None):
    """Causal (or full) attention as two Pallas kernels, differentiable.

    q [B, T, H, D]; k, v [B, Tk, Hkv, D] with H a multiple of Hkv: grouped
    queries are served inside the kernels (query head h reads KV head
    h // (H / Hkv); dk and dv come back at Hkv heads), so do NOT expand K
    and V before the call. Forward: `flash_fwd`. Backward (custom_vjp):
    `flash_bwd`, which rebuilds the probabilities from q, k and the
    forward's log-sum-exp and gives dq, dk and dv in one pass. Products
    take the operands as stored (bf16 on the train path) and sum in f32;
    softmax statistics are f32.

    `block_q` / `block_k`: the tile of scores a kernel works on; left None,
    each kernel takes its own default from the shapes (`default_blocks`).
    A passed block that does not divide its sequence raises ValueError, as
    does a default when T is longer than a block and no multiple of 128.
    A head's K and V (forward) and its Q, dO, K, V and f32 dq, dk, dv
    (backward) stay whole in VMEM, which bounds T at about 16k for D = 128
    (ValueError past it); beyond that, split the sequence over chips
    (`ring_attention`).
    """
    if q.shape[2] % k.shape[2] or k.shape != v.shape:
        raise ValueError(f"flash_attention: q heads {q.shape[2]} must be a "
                         f"multiple of the k / v heads {k.shape[2]}, "
                         f"{v.shape[2]}")
    t_q, t_k = q.shape[1], k.shape[1]
    blocks = default_blocks(t_q, t_k, q.shape[-1])
    if block_q is not None or block_k is not None:
        blocks = tuple((min(block_q or bq, t_q), min(block_k or bk, t_k))
                       for bq, bk in blocks)
    for bq, bk in blocks:
        if t_q % bq or t_k % bk:
            raise ValueError(f"seq lens ({t_q},{t_k}) must divide blocks "
                             f"({bq},{bk})")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash_attention_core(q, k, v, causal, sm_scale, blocks,
                                 interpret)


def reference_attention(q, k, v, *, causal: bool = True,
                        sm_scale: float | None = None):
    """Fused-einsum fallback (XLA fuses softmax into the matmuls well enough
    off-TPU)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
