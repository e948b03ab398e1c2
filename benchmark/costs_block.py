"""The bytes ONE call of the paged block-attention kernel needs (one layer,
every slot, one pass of generation by diffusion over blocks), from the live
context. Beside benchmark/costs.py, which holds the other kernels'; kept
with the benchmark so that no PR that claims a gain can change them."""

from __future__ import annotations


def paged_block_bytes(ctx_tokens: float, active: int, block: int,
                      n_kv_heads: int, head_dim: int, n_heads: int,
                      itemsize: int = 2) -> float:
    """The least the call must move: K and V of every slot's committed
    positions (``ctx_tokens`` over the ``active`` slots) and of the block
    itself, once; the block's queries read and its outputs written. Whole
    pages are NOT counted: the least is the live tokens."""
    kv = (ctx_tokens + active * block) * n_kv_heads * head_dim * 2 * itemsize
    qo = active * block * n_heads * head_dim * 2 * itemsize
    return float(kv + qo)
