"""What ONE call of the paged attention kernel needs in a block that has
window layers (models/block.py: query i of a window layer sees key j iff
``0 <= i - j < window``; a full layer every ``j <= i``), from the live
context. Beside benchmark/costs.py, which holds the other kernels'; kept
with the benchmark so that no PR that claims a gain can change them."""

from __future__ import annotations


def paged_read_bytes(live_tokens: float, rows: float, n_kv_heads: int,
                     head_dim: int, n_heads: int, itemsize: int = 2) -> float:
    """The least one call (one layer, every slot) must move: K and V of the
    tokens its rows can see, once (``live_tokens``: over the slots, a full
    layer's contexts, a window layer's ``min(context, window)``), the
    ``rows`` queries read and their outputs written. Whole pages are NOT
    counted: the least is the live tokens."""
    kv = live_tokens * n_kv_heads * head_dim * 2 * itemsize
    qo = rows * n_heads * head_dim * 2 * itemsize
    return float(kv + qo)


def chunk_keys(start: int, tokens: int, window: int) -> int:
    """The keys the rows of a prompt chunk at positions ``[start, start +
    tokens)`` see between them (``window`` 0: a full layer)."""
    seen = start + tokens
    return min(seen, window + tokens - 1) if window else seen


def chunk_pairs(start: int, tokens: int, window: int) -> int:
    """(query, key) pairs of that chunk: row i sees ``start + i + 1`` keys,
    a window layer's at most ``window``."""
    if not window:
        return tokens * start + tokens * (tokens + 1) // 2
    return sum(min(start + i + 1, window) for i in range(tokens))


def paged_chunk_flops(pairs: float, n_heads: int, head_dim: int) -> float:
    """The scores and the weighted values of ``pairs`` (query, key) pairs:
    two products of ``head_dim`` multiply-adds a pair and query head."""
    return 4.0 * pairs * n_heads * head_dim
