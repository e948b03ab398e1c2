"""Flash attention as a Pallas TPU kernel.

The hot op of the transformer stack (SURVEY.md TPU-native note: pallas for the
ops XLA can't fuse). Streaming-softmax tiling keeps the working set in VMEM and
the (block_q × block_k) score matmuls on the MXU; causal blocks that are fully
masked are skipped. Used by models/llama.py (attn_impl="flash") and as the
per-block kernel of parallel/ring_attention.py on TPU.

Falls back to a fused einsum implementation off-TPU; tests run the kernel in
interpreter mode on CPU (pl.pallas_call(interpret=True)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_STATS_LANES = 128  # stats tiles are [block_q, 128] to satisfy TPU tiling


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, sm_scale: float, causal: bool, block_q: int,
                  block_k: int, num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # causal: the whole k-block is in the future of the whole q-block → skip
    needed = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_scr[:, 0]  # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + pv
        m_scr[:, 0] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[:, 0]
        l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / l[:, None]).astype(o_ref.dtype)
        # log-sum-exp per row, consumed by the backward kernels (FA2).
        # Shape [bq, 1]: TPU block tiling wants the last two dims divisible
        # by (8, 128) or equal to the array dims — a trailing singleton
        # axis satisfies that and broadcasts cleanly in the backward.
        lse_ref[0] = (m_scr[:, 0] + jnp.log(l))[:, None]


def _flash_bh(q, k, v, *, causal: bool, sm_scale: float, block_q: int,
              block_k: int, interpret: bool):
    """q,k,v: [BH, T, D] → [BH, T, D]."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_k)
    if t_q % block_q or t_k % block_k:
        raise ValueError(f"seq lens ({t_q},{t_k}) must divide blocks "
                         f"({block_q},{block_k})")
    num_q = t_q // block_q
    num_k = t_k // block_k
    grid = (bh, num_q, num_k)
    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k_blocks=num_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t_q, 1), jnp.float32),  # lse
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_q, d), jnp.float32),             # output acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_scr, dv_scr, *,
                           sm_scale: float, causal: bool, block_q: int,
                           block_k: int, num_q_blocks: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # causal: this whole q-block precedes the k-block → no contribution
    needed = (not causal) or (q_start + block_q - 1 >= k_start)

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        g = g_ref[0]
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])                           # [bq, bk]
        # dv += pᵀ · dO
        dv_scr[:] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = dO · vᵀ ; ds = p (dp - delta) · scale
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0]) * sm_scale).astype(q.dtype)
        # dk += dsᵀ · q
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, sm_scale: float, causal: bool,
                         block_q: int, block_k: int, num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    needed = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        g = g_ref[0]
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0]) * sm_scale).astype(q.dtype)
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_bh(q, k, v, g, lse, delta, *, causal: bool, sm_scale: float,
                  block_q: int, block_k: int, interpret: bool):
    """Pallas flash backward over [BH, T, D] inputs → (dq, dk, dv).

    Two kernels (the canonical FA2 split): dk/dv accumulate over q blocks
    with the k block resident in VMEM; dq accumulates over k blocks. Both
    recompute p from (q, k, lse) — nothing [T, T]-shaped ever exists, and
    every matmul runs on the MXU in the input dtype with fp32 accumulation.
    Replaces a pure-JAX blockwise backward whose [B,H,T,block] fp32
    intermediates ran the train-step backward at ~2% MXU utilization (it
    was ~24% of the whole train step at 1.5B scale)."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_k)
    if t_q % block_q or t_k % block_k:
        raise ValueError(f"seq lens ({t_q},{t_k}) must divide blocks "
                         f"({block_q},{block_k})")
    num_q = t_q // block_q
    num_k = t_k // block_k

    kv_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),   # q
        pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),   # k
        pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),   # v
        pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),   # g
        pl.BlockSpec((1, block_q, 1), lambda b, ki, qi: (b, qi, 0)),   # lse
        pl.BlockSpec((1, block_q, 1), lambda b, ki, qi: (b, qi, 0)),   # delta
    ]
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_q_blocks=num_q),
        grid=(bh, num_k, num_q),
        in_specs=kv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(q, k, v, g, lse, delta)

    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),   # q
        pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),   # k
        pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),   # v
        pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),   # g
        pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),   # lse
        pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),   # delta
    ]
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_k_blocks=num_k),
        grid=(bh, num_q, num_k),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    from jax.ad_checkpoint import checkpoint_name
    b, t, h, d = q.shape
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
    out_bh, lse = _flash_bh(to_bh(q), to_bh(k), to_bh(v), causal=causal,
                            sm_scale=sm_scale, block_q=block_q,
                            block_k=block_k, interpret=interpret)
    out = out_bh.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    # "attn_lse" lets remat policies save the softmax stats so the backward
    # does not re-run the forward kernel just to rebuild them (the output
    # residual aliases the primal, which callers tag "attn").
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_core(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret):
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                      interpret)[0]


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    """Pallas flash-attention backward (FA2): p is recomputed per block from
    (q, k) + the forward's saved log-sum-exp; delta = rowsum(dO · O)."""
    q, k, v, out, lse = res
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
    g_bh = to_bh(g)
    delta = jnp.sum(g_bh.astype(jnp.float32) *
                    to_bh(out).astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, Tq, 1]
    dq, dk, dv = _flash_bwd_bh(
        to_bh(q), to_bh(k), to_bh(v), g_bh, lse, delta,
        causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        interpret=interpret)
    from_bh = lambda x, t: x.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return (from_bh(dq, t_q).astype(q.dtype),
            from_bh(dk, t_k).astype(k.dtype),
            from_bh(dv, t_k).astype(v.dtype))


_flash_attention_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool | None = None):
    """q,k,v: [B, T, H, D] (same H — expand GQA before calling).
    Differentiable: forward is the Pallas kernel, backward a blockwise
    recompute (no [T,T] materialization)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash_attention_core(q, k, v, causal, sm_scale, block_q, block_k,
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
