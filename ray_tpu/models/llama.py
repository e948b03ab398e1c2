"""Llama-3-family transformer, TPU-first.

The flagship model for the BASELINE configs ("Llama-3-8B pretraining … v5p-64",
"Llama-3-8B serving … v5e-16"). The reference has no in-tree model — it
delegates to torch/vLLM; here the model is native JAX so the whole stack
(sharding, ring attention, pipeline, serving KV cache) composes:

- parameters are a pytree with a stacked layer dim and logical axis names, so
  any mesh (DP/FSDP/TP/CP) is a rule-table swap (ray_tpu.parallel.sharding);
- the layer loop is `lax.scan` → O(1) compile size at any depth;
- attention routes to ring attention over the "context" axis for long
  sequences (SURVEY.md §5.7) and to the Pallas flash kernels on TPU, which
  take K and V at their n_kv_heads;
- GQA + RoPE + RMSNorm + SwiGLU, bf16 activations, fp32 RMSNorm accumulation
  (MXU-friendly shapes: head_dim 128, ffn multiples of 1024).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.block import CacheSpec, gqa_expand, head_major


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # attention implementation: "dense" | "ring" | "flash"
    attn_impl: str = "dense"
    remat: bool = True
    # checkpoint policy: "full" recomputes everything; "dots" saves matmul
    # outputs (jax.checkpoint_policies.dots_with_no_batch_dims_saveable) —
    # less recompute, more HBM; "outs" saves only block outputs
    remat_policy: str = "full"
    # cross-entropy chunk (sequence positions whose fp32 logits are live at
    # once); bigger = less scan serialization, more HBM. T (or more) = one
    # chunk, i.e. effectively unchunked.
    ce_chunk: int = 256
    # Rematerialize CE logits in the backward (checkpoint on the CE chunk
    # body). True = recompute the lm_head matmul in bwd, smallest peak HBM.
    # False = keep each chunk's fp32 logits as residuals — one extra
    # B*T*V fp32 tensor live across the backward, but the recompute matmul
    # disappears. Keep True for HBM-tight configs (bigger batch/model per
    # chip).
    ce_remat: bool = True
    # MLP matmul implementation for the TRAIN path: "bf16" (default) or
    # "int8" — dynamic per-tensor symmetric quantization of both operands
    # into the MXU's int8 path (2x bf16 peak on v5e), fp32 accumulation,
    # straight-through bf16 backward. Measured lever from VERDICT r3 item 8.
    mlp_impl: str = "bf16"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


def llama3_8b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama3_1b(**kw) -> LlamaConfig:
    """~1.2B-param config (bench-friendly on one v5e chip)."""
    d = dict(dim=2048, n_layers=16, n_heads=16, n_kv_heads=8, ffn_dim=8192,
             vocab_size=128256)
    d.update(kw)
    return LlamaConfig(**d)


def llama_tiny(**kw) -> LlamaConfig:
    """Test config: runs on the 8-device CPU mesh in seconds."""
    d = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
             ffn_dim=128, max_seq_len=256, dtype=jnp.float32, remat=False)
    d.update(kw)
    return LlamaConfig(**d)


def num_params(cfg: LlamaConfig) -> int:
    per_layer = (cfg.dim * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim
                 + cfg.n_heads * cfg.head_dim * cfg.dim
                 + 3 * cfg.dim * cfg.ffn_dim + 2 * cfg.dim)
    return (cfg.vocab_size * cfg.dim * 2 + cfg.dim
            + cfg.n_layers * per_layer)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_params(rng, cfg: LlamaConfig):
    """Stacked-layer param pytree. Weight layout keeps the contraction dim
    first so matmuls hit the MXU without transposes."""
    k_embed, k_layers, k_out = jax.random.split(rng, 3)
    hd = cfg.head_dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(cfg.dtype)

    def layer(key):
        ks = jax.random.split(key, 7)
        return {
            "attn": {
                "wq": dense(ks[0], (cfg.dim, cfg.n_heads, hd), cfg.dim),
                "wk": dense(ks[1], (cfg.dim, cfg.n_kv_heads, hd), cfg.dim),
                "wv": dense(ks[2], (cfg.dim, cfg.n_kv_heads, hd), cfg.dim),
                "wo": dense(ks[3], (cfg.n_heads, hd, cfg.dim), cfg.dim),
            },
            "mlp": {
                "w_gate": dense(ks[4], (cfg.dim, cfg.ffn_dim), cfg.dim),
                "w_up": dense(ks[5], (cfg.dim, cfg.ffn_dim), cfg.dim),
                "w_down": dense(ks[6], (cfg.ffn_dim, cfg.dim), cfg.ffn_dim),
            },
            "attn_norm": jnp.ones((cfg.dim,), jnp.float32),
            "mlp_norm": jnp.ones((cfg.dim,), jnp.float32),
        }

    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(layer)(layer_keys)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim),
        "layers": layers,
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "lm_head": dense(k_out, (cfg.dim, cfg.vocab_size), cfg.dim),
    }


def logical_axes(cfg: LlamaConfig):
    """Logical sharding axes, same structure as params (consumed by
    ray_tpu.parallel.sharding.logical_to_shardings)."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn": {
                "wq": ("layers", "embed", "heads", "head_dim"),
                "wk": ("layers", "embed", "kv_heads", "head_dim"),
                "wv": ("layers", "embed", "kv_heads", "head_dim"),
                "wo": ("layers", "heads", "head_dim", "embed"),
            },
            "mlp": {
                "w_gate": ("layers", "embed", "mlp"),
                "w_up": ("layers", "embed", "mlp"),
                "w_down": ("layers", "mlp", "embed"),
            },
            "attn_norm": ("layers", None),
            "mlp_norm": ("layers", None),
        },
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def serve_partition_rules():
    """Serve-side Megatron TP rules over the same tree, consumed by
    parallel.sharding.rule_shardings (ordered; first re.search match
    wins). Column-parallel qkv/gate/up, row-parallel wo/w_down (their
    contractions psum across the axis), vocab-sharded lm_head (argmax
    composes exactly across shards), everything else — embed, norms,
    scalars — replicated. The attention split rides the kv-major GQA
    head order: H/tp query heads are exactly (Hkv/tp) whole kv-head
    groups, so per-head attention math never crosses a shard."""
    from jax.sharding import PartitionSpec as P
    return (
        (r"layers/attn/w[qkv]_hm$", P(None, "tensor", None, None)),
        (r"layers/attn/w[qkv]$", P(None, None, "tensor", None)),
        (r"layers/attn/wo$", P(None, "tensor", None, None)),
        (r"layers/mlp/w_(gate|up)$", P(None, None, "tensor")),
        (r"layers/mlp/w_down$", P(None, "tensor", None)),
        (r"lm_head$", P(None, "tensor")),
        (r".*", P()),
    )


def check_tp_divides(cfg: LlamaConfig, tp: int) -> None:
    """Every dimension serve_partition_rules splits must divide by the
    "tensor" axis size ``tp``."""
    for name in ("n_kv_heads", "n_heads", "ffn_dim", "vocab_size"):
        val = getattr(cfg, name)
        if val % tp:
            raise ValueError(
                f"tp_degree={tp} must divide model {name}={val}")


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * w).astype(x.dtype)


def rope_freqs(cfg: LlamaConfig, positions):
    """positions: [B, T] → (cos, sin) [B, T, head_dim/2], fp32."""
    inv = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, cfg.head_dim, 2, dtype=jnp.float32) / cfg.head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv  # [B,T,hd/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: [B, T, H, D]; rotate pairs (x[..., ::2], x[..., 1::2])."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# the serving block (models/block.py has the contract): the pieces of the
# transformer block the paged programs (serve/llm/kv_cache.py) put together.
# Each sits under a jax.named_scope so that a profiler trace says which
# layer an op belongs to (`norm`, `attn`, `mlp`, `embed`, `lm_head`); the
# scopes are compile-time metadata and change no executable.
# ---------------------------------------------------------------------------

def cache_spec(cfg: LlamaConfig):
    return CacheSpec(paged_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                     head_dim=cfg.head_dim)


def serve_layers(cfg: LlamaConfig):
    """Every layer is the same: the programs scan the stacked layers."""
    return None


def serve_params(params, cfg: LlamaConfig):
    """wq, wk and wv of the stacked layers head-major, [L, H, D, hd]
    (``wq_hm`` ...; models/block.py ``head_major``)."""
    layers = params["layers"]
    return {**params, "layers": {
        **layers, "attn": head_major(layers["attn"], _QKV)}}


def serve_embed(params, tokens, cfg: LlamaConfig):
    return params["embed"][tokens].astype(cfg.dtype)


def serve_qkv(x, layer, cos, sin, cfg: LlamaConfig):
    """Pre-attention norm, the q/k/v projections and RoPE. x: [B,T,D]."""
    a = layer["attn"]
    with jax.named_scope("norm"):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    with jax.named_scope("attn"):
        q = jnp.einsum("btd,hdk->bthk", h, a["wq_hm"])
        k = jnp.einsum("btd,hdk->bthk", h, a["wk_hm"])
        v = jnp.einsum("btd,hdk->bthk", h, a["wv_hm"])
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def serve_attn_out(attn, layer):
    """The attention output projection of attn [..., H, hd]."""
    return jnp.einsum("...hk,hkd->...d", attn, layer["attn"]["wo"])


def serve_ffn(x, layer, cfg: LlamaConfig, ld=None):
    """x + SwiGLU(norm(x)); no expert choice to report."""
    with jax.named_scope("norm"):
        h2 = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(h2 @ layer["mlp"]["w_gate"])
        up = h2 @ layer["mlp"]["w_up"]
        return x + (gate * up) @ layer["mlp"]["w_down"], None


def serve_final_norm(x, params, cfg: LlamaConfig):
    with jax.named_scope("norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def serve_lm_head(x, params, cfg: LlamaConfig):
    """The output projection, float32 logits."""
    with jax.named_scope("lm_head"):
        return (x @ params["lm_head"]).astype(jnp.float32)


def _quantize_int8(t):
    """Dynamic per-tensor symmetric quantization: t -> (int8, fp32 scale)."""
    s = (jnp.max(jnp.abs(t)).astype(jnp.float32) / 127.0) + 1e-12
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s


@jax.custom_vjp
def int8_matmul(x, w):
    """x @ w with BOTH operands dynamically quantized to int8 and the
    contraction run on the MXU's int8 path with int32 accumulation
    (~1.55x bf16 matmul throughput measured on one v5e at bench shapes).
    Backward is straight-through bf16 (quantization treated as identity) —
    the standard int8-forward training recipe."""
    out, _ = _int8_matmul_fwd(x, w)
    return out


def _int8_matmul_fwd(x, w):
    xq, xs = _quantize_int8(x)
    wq, ws = _quantize_int8(w)
    acc = jax.lax.dot_general(
        xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = (acc.astype(jnp.float32) * (xs * ws)).astype(x.dtype)
    # save the QUANTIZED residuals: int8 + scale is half of bf16, which is
    # what lets the int8 path fit where saved-bf16 residuals OOM (measured:
    # +245MB over budget at dots-remat b4 with bf16 residuals). Backward
    # uses the dequantized approximations — consistent with the straight-
    # through estimator the forward already commits to.
    return out, (xq, xs, wq, ws)


def _int8_matmul_bwd(res, g):
    xq, xs, wq, ws = res
    # gradients arrive at the model dtype; dequantized operands join at it
    x = (xq.astype(jnp.float32) * xs).astype(g.dtype)
    w = (wq.astype(jnp.float32) * ws).astype(g.dtype)
    dx = jnp.einsum("...n,kn->...k", g, w)
    dw = jnp.einsum("...k,...n->kn", x, g)
    return dx.astype(g.dtype), dw.astype(g.dtype)


int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)


def _mlp_matmul(h, w, cfg: LlamaConfig):
    if cfg.mlp_impl == "int8":
        return int8_matmul(h, w)
    return h @ w


def _attention(q, k, v, cfg: LlamaConfig, mesh, *, positions_offset=0):
    """Causal self-attention dispatch: ring over the context axis, Pallas
    flash on TPU, einsum fallback."""
    n_rep = cfg.n_heads // cfg.n_kv_heads
    if cfg.attn_impl == "flash":
        from ray_tpu.ops.attention import flash_attention
        attn = functools.partial(flash_attention, causal=True)
        if mesh is not None and mesh.size > 1:
            # a compiled Pallas kernel is opaque to GSPMD ("Mosaic kernels
            # cannot be automatically partitioned"): run it per shard.
            # Attention is independent per sequence and per head, so batch
            # splits over the data-parallel axes and heads over "tensor"
            # with no collective.
            from jax.sharding import PartitionSpec as P

            from ray_tpu.parallel.sharding import batch_sharding
            tensor = mesh.shape.get("tensor", 1)
            if cfg.n_kv_heads % tensor:
                # a shard of query heads must find its KV heads on its
                # own chip; where they do not split, every head gets one
                k, v = gqa_expand(k, n_rep), gqa_expand(v, n_rep)
            heads = "tensor" if tensor > 1 else None
            spec = P(batch_sharding(mesh).spec[0], None, heads, None)
            attn = jax.shard_map(attn, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check_vma=False)
        # the kernels serve grouped queries themselves: K and V go in at
        # the heads they have
        return attn(q, k, v)
    k = gqa_expand(k, n_rep)
    v = gqa_expand(v, n_rep)
    if cfg.attn_impl == "ring" and mesh is not None:
        from ray_tpu.parallel.ring_attention import ring_attention
        return ring_attention(q, k, v, mesh, causal=True)
    sm = cfg.head_dim ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm
    t_q, t_k = q.shape[1], k.shape[1]
    q_pos = positions_offset + jnp.arange(t_q)
    mask = q_pos[:, None] >= jnp.arange(t_k)[None, :]
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


_QKV = ("wq", "wk", "wv")


def _project_qkv(h, w):
    return tuple(jnp.einsum("btd,dhk->bthk", h, w[name]) for name in _QKV)


def _pack_qkv(w):
    """wq, wk and wv of one layer as one array [D, n_kv_heads, n_rep + 2,
    hd]: a KV head's group of query heads, its key head and its value head
    side by side, so that ONE collective moves all three (the chip's
    compiler keeps one all-gather in flight and ran the second of three,
    side by side, synchronously) and a split of the heads over "tensor"
    stays whole groups on both sides."""
    d, groups, hd = w["wk"].shape
    return jnp.concatenate(
        [w["wq"].reshape(d, groups, -1, hd),
         w["wk"][:, :, None], w["wv"][:, :, None]], axis=2)


def _unpack_qkv(packed):
    d, _, group, hd = packed.shape
    return {"wq": packed[:, :, :group - 2].reshape(d, -1, hd),
            "wk": packed[:, :, group - 2], "wv": packed[:, :, group - 1]}


def _batch_axes(mesh) -> tuple:
    """The mesh axes that split the batch, where "fsdp" is one of them and
    so splits the weights too; () where there is nothing to gather."""
    if mesh is None:
        return ()
    from ray_tpu.parallel.sharding import batch_sharding
    axes = batch_sharding(mesh).spec[0] or ()
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    return axes if "fsdp" in axes else ()


def _per_shard(f, mesh, in_specs, out_specs):
    """f(*blocks, axes) on every data-parallel shard's own blocks, the
    other mesh axes (tensor, context) left to the compiler; on the whole
    arrays, with no axis to name, where "fsdp" splits nothing. A spec names
    what splits an argument's first dimension: "batch" or "fsdp"."""
    from jax.sharding import PartitionSpec as P
    axes = _batch_axes(mesh)
    f = functools.partial(f, axes=axes)
    if not axes:
        return f
    spec = {"batch": P(axes), "fsdp": P("fsdp")}
    return jax.shard_map(
        f, mesh=mesh, in_specs=jax.tree.map(spec.get, in_specs),
        out_specs=jax.tree.map(spec.get, out_specs),
        axis_names=frozenset(axes), check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather_block(packed, axes):
    # [D / fsdp, ...] -> [1, D, ...]: the shard's own copy of the whole
    if axes:
        packed = jax.lax.all_gather(packed, "fsdp", axis=0, tiled=True)
    return packed[None]


def _gather_block_fwd(packed, axes):
    return _gather_block(packed, axes), None


def _gather_block_bwd(axes, _, ct):
    """The gather's transpose, a reduce-scatter, as a ring of permutes:
    the chip's compiler runs a reduce-scatter op synchronously wherever it
    stands, and permutes behind the compute beside them. Shard d ends with
    block d of the sum; at step s it adds its own block d - 1 - s to what
    shard d - 1 hands it. bf16 sums a hop, as the compiler's own ring
    inside a product keeps them."""
    ct = ct[0]
    if not axes:
        return ct,
    n = jax.lax.axis_size("fsdp")
    me = jax.lax.axis_index("fsdp")
    blocks = ct.reshape(n, ct.shape[0] // n, *ct.shape[1:])

    def block(s):
        return jax.lax.dynamic_index_in_dim(blocks, (me - 1 - s) % n,
                                            keepdims=False)

    acc = block(0)
    for s in range(1, n):
        acc = jax.lax.ppermute(
            acc, "fsdp", [(j, (j + 1) % n) for j in range(n)]) + block(s)
    return acc,


_gather_block.defvjp(_gather_block_fwd, _gather_block_bwd)


def _project_block(h, w_ahead, axes):
    return _project_qkv(h, _unpack_qkv(w_ahead[0]))


def _dw_block(h, ct, axes):
    # the shard's own tokens' share of the weights' cotangent, not yet
    # summed over "fsdp": that sum is the transpose of _gather_block
    like = {name: jax.ShapeDtypeStruct((h.shape[-1],) + c.shape[2:], h.dtype)
            for name, c in zip(_QKV, ct)}
    dw, = jax.linear_transpose(lambda w: _project_qkv(h, w), like)(ct)
    dw = _pack_qkv(dw)
    others = tuple(a for a in axes if a != "fsdp")
    return (jax.lax.psum(dw, others) if others else dw)[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _qkv_ahead(mesh, h, w, w_ahead):
    """The q, k and v projections of h [B,T,D]. ``w_ahead`` [fsdp, D,
    n_kv_heads, n_rep + 2, hd] is every "fsdp" shard's own copy of the
    layer's packed wq / wk / wv, gathered while the layer before still ran
    (hidden_states), so the forward products wait for nothing. The weights'
    cotangent goes back the same way: each shard's share of it, unsummed,
    is ``w_ahead``'s cotangent, the scan's transpose carries it into the
    next layer's backward, and the gather's transpose sums and scatters it
    there with that whole layer as cover (it is the LAST thing a layer's
    backward produces; reduced in place it is waited for). ``w``, the
    layer's shards as the parameters hold them, is the residual instead of
    the gathered copy: dh is the product on ``w`` that the parent ran
    (FSDP's second gather, the whole layer's backward in front of it), and
    ``w`` itself gets no cotangent from here."""
    return _per_shard(_project_block, mesh, ("batch", "fsdp"),
                      "batch")(h, w_ahead)


def _qkv_ahead_fwd(mesh, h, w, w_ahead):
    return _qkv_ahead(mesh, h, w, w_ahead), (h, w)


def _qkv_ahead_bwd(mesh, res, ct):
    h, w = res
    dh, = jax.vjp(lambda h: _project_qkv(h, w), h)[1](ct)
    dw_ahead = _per_shard(_dw_block, mesh, ("batch", "batch"), "fsdp")(h, ct)
    return dh, None, dw_ahead


_qkv_ahead.defvjp(_qkv_ahead_fwd, _qkv_ahead_bwd)


def _layer_fwd(x, layer, w_ahead, cos, sin, cfg: LlamaConfig, mesh):
    # the named scopes (norm / attn / mlp here, embed / lm_head / loss
    # around them) are what a profiler trace names an op's layer by; the
    # serving steps in serve/llm/kv_cache.py use the same names
    from jax.ad_checkpoint import checkpoint_name
    with jax.named_scope("norm"):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    with jax.named_scope("attn"):
        q, k, v = _qkv_ahead(mesh, h, jax.lax.stop_gradient(
            {n: layer["attn"][n] for n in _QKV}), w_ahead)
        q = checkpoint_name(q, "q_proj")
        k = checkpoint_name(k, "k_proj")
        v = checkpoint_name(v, "v_proj")
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        attn = checkpoint_name(_attention(q, k, v, cfg, mesh), "attn")
        attn_out = checkpoint_name(
            jnp.einsum("bthk,hkd->btd", attn, layer["attn"]["wo"]),
            "attn_out")
        x = x + attn_out
    with jax.named_scope("norm"):
        h = checkpoint_name(
            rms_norm(x, layer["mlp_norm"], cfg.norm_eps), "mlp_in")
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(_mlp_matmul(h, layer["mlp"]["w_gate"], cfg))
        up = _mlp_matmul(h, layer["mlp"]["w_up"], cfg)
        x = x + checkpoint_name(
            _mlp_matmul(gate * up, layer["mlp"]["w_down"], cfg), "mlp_out")
    return x


def _remat(body, cfg: LlamaConfig):
    """Wrap a scan body in jax.checkpoint per cfg.remat_policy.

    "full": recompute everything (min HBM, ~4/3x matmul FLOPs).
    "dots": save every matmul output — includes the d_ff-wide MLP
        intermediates, ~0.5 GB/layer at B8/T2048/d2048 (OOMs one v5e at
        1.5B params even with adafactor).
    "outs": save only the residual-stream contributions (attn_out/mlp_out,
        checkpoint_name'd above) — 1/8 the HBM of "dots"; the backward
        re-runs QKV+attention+MLP but reuses the saved block outputs.
    "hybrid": save everything EXCEPT the d_ff-wide gate/up intermediates
        (q/k/v, attention + its softmax stats, attn_out, mlp_in, mlp_out — ~1/3 the HBM of
        "dots"): the backward recomputes only the two wide MLP matmuls
        (~0.4x of one forward), trading a small FLOPs tax for the HBM to
        run batch 8 where "dots" caps at 4 — narrower than the MXU likes.
        (The standard selective-checkpointing middle ground between "save
        all dots" and "save block outputs".)"""
    if cfg.remat_policy == "dots":
        # q / k / v by name as well: their products sit inside _qkv_ahead's
        # shard_map, where the policy does not look
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "q_proj", "k_proj", "v_proj")))
    if cfg.remat_policy == "hybrid":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "q_proj", "k_proj", "v_proj", "attn", "attn_lse", "attn_out",
                "mlp_in", "mlp_out"))
    if cfg.remat_policy == "outs":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_out"))
    return jax.checkpoint(body)


def forward(params, tokens, cfg: LlamaConfig, mesh=None):
    """tokens [B, T] → logits [B, T, vocab]."""
    x = hidden_states(params, tokens, cfg, mesh)
    with jax.named_scope("lm_head"):
        return (x @ params["lm_head"]).astype(jnp.float32)


def hidden_states(params, tokens, cfg: LlamaConfig, mesh=None):
    """tokens [B, T] → final-norm hidden states [B, T, D] (no lm_head)."""
    b, t = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        cos, sin = rope_freqs(cfg, positions)

    # The first thing a layer computes is its q / k / v projections and the
    # last thing its backward produces is their weights' cotangent, and a
    # scan iteration can neither ask for anything before it starts nor
    # leave a sum running when it ends: gathered and reduced inside their
    # own layer, these three weights are waited for twice a layer. So the
    # carry holds them for the layer about to run, gathered while the layer
    # before ran, each iteration asks for the next layer's, and the scan's
    # transpose hands the unsummed cotangent on to where the gather was
    # asked (_qkv_ahead). The index into the stacked shards transposes to
    # an update in place.
    stack = {n: params["layers"]["attn"][n] for n in _QKV}
    gather = _per_shard(_gather_block, mesh, "fsdp", "fsdp")

    def ahead(i):
        # under the layer's scope: a trace counts moving these weights, and
        # summing their cotangent, as the attention's
        with jax.named_scope("attn"):
            return gather(_pack_qkv(jax.tree.map(
                lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False),
                stack)))

    def layer_fwd(x, layer, w_ahead):
        return _layer_fwd(x, layer, w_ahead, cos, sin, cfg, mesh)

    if cfg.remat:
        layer_fwd = _remat(layer_fwd, cfg)
    last = cfg.n_layers - 1

    def body(carry, xs):
        (x, w_ahead), (layer, i) = carry, xs
        # the last layer asks for its own again: one gather a step wasted
        return (layer_fwd(x, layer, w_ahead),
                ahead(jnp.minimum(i + 1, last))), None

    (x, _), _ = jax.lax.scan(body, (x, ahead(0)),
                             (params["layers"], jnp.arange(cfg.n_layers)))
    with jax.named_scope("norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def chunked_cross_entropy(lm_head, hidden, targets, chunk: int = 256,
                          remat: bool = True):
    """Next-token CE without ever materializing fp32 [B, T, vocab].

    The naive log_softmax over the full sequence allocates B·T·V fp32 —
    7.8 GiB at B=8, T=2048, V=128k, more than half a v5e's HBM. Scanning
    sequence chunks keeps the live logits at B·chunk·V and lets XLA overlap
    the lm_head matmul of one chunk with the reduction of the previous.

    ``remat=False`` drops the checkpoint: each chunk's fp32 logits persist
    as backward residuals (full B·T·V again, but live only across the CE
    backward region) in exchange for skipping the lm_head recompute matmul
    (see LlamaConfig.ce_remat).
    """
    b, t, d = hidden.shape
    chunk = min(chunk, t)
    n = -(-t // chunk)  # pad the tail: next-token CE always sees t = T-1,
    # which is never divisible by a power-of-two chunk — an exact-division
    # fallback would silently collapse to one full-logits chunk
    pad = n * chunk - t
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    hid = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    tgt = targets.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(acc, xs):
        h, y = xs
        with jax.named_scope("lm_head"):
            logits = (h @ lm_head).astype(jnp.float32)   # [B, chunk, V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(y, 0)[..., None], axis=-1)[..., 0] - lse
        ll = jnp.where(y >= 0, ll, 0.0)  # padded positions contribute 0
        return acc + jnp.sum(ll), None

    if remat:
        # checkpoint: without it the scan's backward saves EVERY chunk's
        # fp32 logits as residuals — the full B·T·V tensor again
        body = jax.checkpoint(body)
    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hid, tgt))
    return -total / (b * t)


def loss_fn(params, batch, cfg: LlamaConfig, mesh=None):
    """Next-token cross-entropy; batch: {"tokens": [B, T+1]} or tokens array."""
    tokens = batch["tokens"] if isinstance(batch, dict) else batch
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    hidden = hidden_states(params, inputs, cfg, mesh)
    with jax.named_scope("loss"):
        return chunked_cross_entropy(params["lm_head"], hidden, targets,
                                     chunk=cfg.ce_chunk, remat=cfg.ce_remat)


# ---------------------------------------------------------------------------
# checkpoint io (flat-npz format; the serving engine's checkpoint_path and
# offline eval both read it — reference models load torch/safetensors via
# vLLM; here the canonical on-disk form is a flattened jax pytree)
# ---------------------------------------------------------------------------

def _flatten_params(params, prefix=""):
    out = {}
    if isinstance(params, dict):
        for k, v in params.items():
            out.update(_flatten_params(v, f"{prefix}{k}/"))
        return out
    out[prefix.rstrip("/")] = np.asarray(params)
    return out


def save_params(params, path: str) -> str:
    """Write params as ONE .npz of flattened pytree paths (atomic rename).
    `path` may be a file ('x.npz') or a directory (-> dir/params.npz)."""
    import os
    if not path.endswith(".npz"):
        os.makedirs(path, exist_ok=True)
        path = os.path.join(path, "params.npz")
    tmp = path + ".tmp.npz"  # keep the suffix: np.savez appends it otherwise
    try:
        np.savez(tmp, **_flatten_params(params))
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return path


def load_params(path: str, cfg: LlamaConfig | None = None):
    """Load a save_params checkpoint back into the nested pytree. With a
    cfg, shapes are validated against a fresh init's structure."""
    import os
    if os.path.isdir(path):
        path = os.path.join(path, "params.npz")
    flat = np.load(path)
    params: dict = {}
    for key in flat.files:
        node = params
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.asarray(flat[key])
    if cfg is not None:
        expect = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
        exp_flat = _flatten_params_shapes(expect)
        got_flat = {k: tuple(np.asarray(flat[k]).shape) for k in flat.files}
        if exp_flat != got_flat:
            missing = set(exp_flat) - set(got_flat)
            extra = set(got_flat) - set(exp_flat)
            mismatched = {k for k in set(exp_flat) & set(got_flat)
                          if exp_flat[k] != got_flat[k]}
            raise ValueError(
                f"checkpoint does not match config: missing={sorted(missing)[:5]} "
                f"extra={sorted(extra)[:5]} shape-mismatch={sorted(mismatched)[:5]}")
    return params


def _flatten_params_shapes(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten_params_shapes(v, f"{prefix}{k}/"))
        return out
    out[prefix.rstrip("/")] = tuple(tree.shape)
    return out
