"""The operations and bytes ONE grouped expert call needs (a routed layer's
SwiGLU experts over the rows routed to them), from rows, experts touched
and widths. Beside benchmark/costs.py, which holds the dense kernels'; kept
with the benchmark so that no PR that claims a gain can change them."""

from __future__ import annotations


def grouped_ffn_flops(rows: float, dim: int, expert_dim: int) -> float:
    """Three products a row (gate, up, down), 2 * dim * expert_dim each.
    ``rows``: (token, expert) picks, i.e. tokens x experts per token."""
    return 3.0 * 2.0 * rows * dim * expert_dim


def grouped_ffn_bytes(rows: float, experts_touched: float, dim: int,
                      expert_dim: int, itemsize: int = 2) -> float:
    """The least the call must move: the three matrices of every expert
    TOUCHED, once (an expert no row chose costs nothing), each row read
    once and its result written once. Intermediates are not counted: a
    fused call need not put them in HBM."""
    weights = experts_touched * 3.0 * dim * expert_dim * itemsize
    return weights + rows * 2.0 * dim * itemsize
