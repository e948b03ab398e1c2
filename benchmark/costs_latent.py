"""The operations and bytes ONE call of the paged kernel on a LATENT pool
needs (one layer, every slot: the absorbed form of a latent-attention
mixer), from the live context. Beside benchmark/costs.py, which holds the
other kernels'; kept with the benchmark so that no PR that claims a gain
can change them."""

from __future__ import annotations


def paged_latent_bytes(ctx_tokens: float, active: int, n_heads: int,
                       latent_dim: int, value_dim: int,
                       itemsize: int = 2) -> float:
    """The least the call must move: every slot's live rows ONCE (a row is
    key and value at once: ``latent_dim`` numbers a token), each slot's
    ``n_heads`` query rows of ``latent_dim`` read and its output rows of
    ``value_dim`` written. Padding lanes and whole pages are NOT counted,
    so both show as lost share."""
    rows = ctx_tokens * latent_dim * itemsize
    qo = active * n_heads * (latent_dim + value_dim) * itemsize
    return float(rows + qo)


def paged_latent_flops(ctx_tokens: float, n_heads: int, latent_dim: int,
                       value_dim: int) -> float:
    """Every head's scores over ``latent_dim`` lanes and its weighted sum
    over ``value_dim`` lanes, against every live row."""
    return 2.0 * ctx_tokens * n_heads * (latent_dim + value_dim)
