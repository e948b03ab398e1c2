"""What ONE call of the paged attention kernel needs in a block whose key
rows and value rows differ in width and whose KV-head count differs by layer
kind (models/block.py: ``CacheSpec.value_dim``, ``window_kv_heads``), from
the live context: the PUBLISHED lanes (a key row of 192 stored on 256 shows
as lost share of the roofline, not as work), live tokens, no whole pages.
Beside benchmark/costs_window.py, whose ``chunk_keys`` / ``chunk_pairs`` it
shares and whose byte count gives every layer one head shape; kept with the
benchmark so that no PR that claims a gain can change them."""

from __future__ import annotations


def layer_shape(sz: dict, ringed: bool) -> tuple | None:
    """(KV heads, key lanes, value lanes, query heads) of a window layer
    (``ringed``) or a full layer, from a family's ``sizes``; None where the
    sizes state no value width or window KV heads of their own."""
    if not (sz.get("value_dim") and sz.get("window_kv_heads")):
        return None
    return (sz["window_kv_heads"] if ringed else sz["n_kv_heads"],
            sz["head_dim"], sz["value_dim"], sz["n_heads"])


def paged_read_bytes(live_tokens: float, rows: float, n_kv_heads: int,
                     key_dim: int, value_dim: int, n_heads: int,
                     itemsize: int = 2) -> float:
    """The least one call (one layer, every slot) must move: K of
    ``live_tokens x n_kv_heads x key_dim`` and V of ``.. x value_dim`` once
    (``live_tokens``: over the slots, a full layer's contexts, a window
    layer's ``min(context, window)``), the ``rows`` queries read (``n_heads
    x key_dim``) and their outputs written (``n_heads x value_dim``)."""
    return float((live_tokens * n_kv_heads + rows * n_heads)
                 * (key_dim + value_dim) * itemsize)


def paged_chunk_flops(pairs: float, n_heads: int, key_dim: int,
                      value_dim: int) -> float:
    """The scores (``key_dim`` multiply-adds a pair and query head) and the
    weighted values (``value_dim``) of ``pairs`` (query, key) pairs."""
    return 2.0 * pairs * n_heads * (key_dim + value_dim)
