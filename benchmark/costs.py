"""The operations and bytes the kernels need, from shapes. Kept with the
benchmark so that no PR that claims a gain can change them. A model
family's own counts (parameters, FLOPs a token) are in its adapter under
benchmark/models/."""

from __future__ import annotations


def flash_attention_flops(batch: int, seq_len: int, n_heads: int,
                          head_dim: int) -> dict:
    """Causal attention of one layer: forward is QK^T and PV over half the
    square (2 * 2 * B * H * T^2 * hd / 2); backward recomputes the scores
    and takes four more products (2.5x forward)."""
    fwd = 2.0 * batch * n_heads * seq_len * seq_len * head_dim
    return {"fwd": fwd, "bwd": 2.5 * fwd}


def flash_attention_bytes(batch: int, seq_len: int, n_heads: int,
                          head_dim: int, itemsize: int = 2) -> dict:
    """q, k, v read and o written once forward (k, v already expanded to
    n_heads, as the kernel is called); backward reads q, k, v, o, do and
    writes dq, dk, dv."""
    one = batch * seq_len * n_heads * head_dim * itemsize
    return {"fwd": 4.0 * one, "bwd": 8.0 * one}


def paged_decode_bytes(context_lens: list[int], n_kv_heads: int,
                       head_dim: int, n_heads: int,
                       itemsize: int = 2) -> float:
    """One call (one layer, every slot): each slot's live K and V once,
    its query read and its output written. Whole pages are NOT counted:
    the least the kernel must move is the live tokens."""
    kv = sum(context_lens) * n_kv_heads * head_dim * 2 * itemsize
    qo = len(context_lens) * n_heads * head_dim * 2 * itemsize
    return float(kv + qo)


def roofline_s(flops: float, bytes_: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which side bounds it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "bandwidth")
