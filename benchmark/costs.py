"""The operations and bytes the algorithm needs, from shapes. Kept with
the benchmark so that no PR that claims a gain can change them."""

from __future__ import annotations


def dense_params(sz: dict) -> int:
    """Parameters of the dense block stack, embedding and output head."""
    hd = sz["dim"] // sz["n_heads"]
    per_layer = (sz["dim"] * (sz["n_heads"] + 2 * sz["n_kv_heads"]) * hd
                 + sz["n_heads"] * hd * sz["dim"]
                 + 3 * sz["dim"] * sz["ffn_dim"] + 2 * sz["dim"])
    return 2 * sz["vocab_size"] * sz["dim"] + sz["dim"] \
        + sz["n_layers"] * per_layer


def train_flops_per_token(sz: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one token needs: 6 per parameter that
    multiplies it (the embedding table is a lookup, so it is left out)
    plus causal attention, 6 * layers * seq_len * dim (QK^T and PV, half
    the square, forward 2x + backward 4x). Recomputation is not counted."""
    matmul_params = dense_params(sz) - sz["vocab_size"] * sz["dim"]
    return 6.0 * matmul_params + 6.0 * sz["n_layers"] * seq_len * sz["dim"]


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
