"""SDAR-MoE (``model_type`` ``sdar_moe``, e.g. JetLM/SDAR-30B-A3B-Chat): a
pre-norm residual block of grouped-query attention and routed experts that
GENERATES BY DIFFUSION OVER BLOCKS: positions are cut into blocks of
``block_length`` B from position 0, attention is causal from block to block
and bidirectional inside one, and a block of B tokens comes out of
``denoise_passes`` passes over its B positions.

Every layer alike: ``h = x + Attn(rms(x))``, ``y = h + MoE(rms(h))``; one
RMSNorm after the last layer, then the head (its own matrix, not the
embedding). RMSNorm ``w * x / sqrt(mean(x^2) + eps)``.

- attention: ``q = RoPE(rms(z W_q))``, ``k = RoPE(rms(z W_k))`` with one
  RMSNorm weight of ``head_dim`` each, applied per head BEFORE the rotation
  (on all lanes, at the absolute position; lanes paired as
  ``llama.apply_rope`` pairs them); scores scaled by ``head_dim ** -0.5``;
  query head h reads KV head ``h // (n_heads / n_kv_heads)``; key j is
  visible to query i iff ``j // B <= i // B``; ``W_o``. No bias.
- experts: ``p = softmax(z W_r)`` over all experts in float32; the
  ``top_k`` most probable; weights ``p_e / sum of the chosen`` where
  ``norm_topk_prob``; output ``sum_e w_e SwiGLU_e(z)``. No shared expert, no
  bias, no selection bias (parallel/expert.py).
- generation (the engine's block program, serve/llm/engine.py): a block
  starts as its known tokens (what a prompt leaves over after its whole
  blocks) followed by ``mask_token_id``; a denoise pass runs its B positions
  against the cached blocks and itself, keeps no K / V, and reveals the
  ``reveal_per_pass`` masked positions whose best token is most probable
  (greedy at temperature 0; the mask token's logit is left out: it is never
  produced); after ``denoise_passes`` passes no mask is left, and the clean
  block's K / V are kept by the pass that runs it once more: the first
  denoise pass of the NEXT block, over both blocks at once (the deferred
  commit; a stream's last block is never committed). The logits AT a
  masked position are the distribution of the token that belongs there.

Parameters are a LIST of layers, each weight its own array, and the paged
programs WALK it (``serve_layers``), though every layer is alike: the
grouped expert product is a Pallas kernel, whose operands are materialised
buffers, so a scan over stacked weights copies every layer's three expert
matrices (1.2 GB) out of the stack for every pass (measured: 58 % of the
device's time, PERF.md section 6, PR 37); a list is read where it lies.
This module is the architecture's serving block (models/block.py has the
contract).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.block import CacheSpec, LayerDef, head_major
from ray_tpu.models.llama import apply_rope, rms_norm, rope_freqs  # noqa: F401
from ray_tpu.parallel import expert as expert_mod


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 128
    top_k: int = 8
    expert_dim: int = 768
    max_seq_len: int = 2048
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    # generation settings, kept with the model as a generation config is
    block_length: int = 4
    mask_token_id: int = 151669
    denoise_passes: int = 2
    dtype: Any = jnp.bfloat16

    @property
    def reveal_per_pass(self) -> int:
        """Masked positions a denoise pass turns into tokens."""
        return -(-self.block_length // self.denoise_passes)


def sdar_moe_tiny(**kw) -> SdarMoeConfig:
    """Test config: heads of 16, 8 experts of 32 top-2, blocks of 4."""
    d = dict(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
             head_dim=16, n_experts=8, top_k=2, expert_dim=32,
             max_seq_len=192, rope_theta=10000.0, mask_token_id=511,
             dtype=jnp.float32)
    d.update(kw)
    return SdarMoeConfig(**d)


def num_params(cfg: SdarMoeConfig) -> int:
    d, hd = cfg.dim, cfg.head_dim
    layer = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd \
        + cfg.n_heads * hd * d + 2 * hd + 2 * d \
        + d * cfg.n_experts + 3 * cfg.n_experts * d * cfg.expert_dim
    return 2 * cfg.vocab_size * d + d + cfg.n_layers * layer


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=1)
def init_params(key, cfg: SdarMoeConfig):
    """Normal, std 1/sqrt(fan_in), made under jit in the served dtype (no
    float32 copy of the model beside the weights)."""
    dt = jnp.dtype(cfg.dtype)

    def w(k, *shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    d, h, hkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    e, f = cfg.n_experts, cfg.expert_dim
    k_layers, k_embed, k_head = jax.random.split(key, 3)

    def layer(key):
        k = jax.random.split(key, 8)
        return {
            "attn_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt),
            "attn": {"wq": w(k[0], d, h, hd, fan_in=d),
                     "wk": w(k[1], d, hkv, hd, fan_in=d),
                     "wv": w(k[2], d, hkv, hd, fan_in=d),
                     "wo": w(k[3], h, hd, d, fan_in=h * hd),
                     "q_norm": jnp.ones((hd,), dt),
                     "k_norm": jnp.ones((hd,), dt)},
            "moe": {"router": w(k[4], d, e, fan_in=d),
                    "w_gate": w(k[5], e, d, f, fan_in=d),
                    "w_up": w(k[6], e, d, f, fan_in=d),
                    "w_down": w(k[7], e, f, d, fan_in=f)}}

    return {"embed": w(k_embed, cfg.vocab_size, d, fan_in=d),
            "layers": [layer(k) for k in jax.random.split(k_layers,
                                                          cfg.n_layers)],
            "final_norm": jnp.ones((d,), dt),
            "lm_head": w(k_head, d, cfg.vocab_size, fan_in=d)}


def load_params(path: str, cfg: SdarMoeConfig | None = None):
    raise NotImplementedError(
        "sdar_moe has no checkpoint reader yet: serve it on seeded weights "
        "(checkpoint_path=None)")


_NO_TP = ("sdar_moe has no tensor-parallel partition rules yet (the experts "
          "need their own): tp_degree must be 1")


def check_tp_divides(cfg: SdarMoeConfig, tp: int) -> None:
    if tp != 1:
        raise ValueError(_NO_TP)


def serve_partition_rules():
    raise ValueError(_NO_TP)


# ---------------------------------------------------------------------------
# the serving block (models/block.py)
# ---------------------------------------------------------------------------

def cache_spec(cfg: SdarMoeConfig) -> CacheSpec:
    return CacheSpec(
        paged_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, routed_layers=cfg.n_layers, top_k=cfg.top_k,
        n_experts=cfg.n_experts, block_length=cfg.block_length,
        mask_token=cfg.mask_token_id)


def serve_layers(cfg: SdarMoeConfig) -> tuple:
    """Every layer attends and routes; walked, not scanned (module
    docstring): layer i owns row i of the pool and of the routing record."""
    return tuple(LayerDef(mixer="attn", ffn="routed", page_layer=i,
                          routed_layer=i) for i in range(cfg.n_layers))


def serve_params(params, cfg: SdarMoeConfig):
    """wq, wk and wv of every layer head-major, [H, D, hd] (``wq_hm`` ...;
    models/block.py ``head_major``)."""
    return {**params, "layers": [
        {**lp, "attn": head_major(lp["attn"], ("wq", "wk", "wv"))}
        for lp in params["layers"]]}


def serve_embed(params, tokens, cfg: SdarMoeConfig):
    return params["embed"][tokens].astype(cfg.dtype)


def serve_qkv(x, layer, cos, sin, cfg: SdarMoeConfig):
    """Pre-attention norm, q/k/v projections, the per-head q/k norm, RoPE."""
    a = layer["attn"]
    with jax.named_scope("norm"):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    with jax.named_scope("attn"):
        q = jnp.einsum("btd,hdk->bthk", h, a["wq_hm"])
        k = jnp.einsum("btd,hdk->bthk", h, a["wk_hm"])
        v = jnp.einsum("btd,hdk->bthk", h, a["wv_hm"])
        q = rms_norm(q, a["q_norm"], cfg.norm_eps)
        k = rms_norm(k, a["k_norm"], cfg.norm_eps)
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def serve_attn_out(attn, layer):
    return jnp.einsum("...hk,hkd->...d", attn, layer["attn"]["wo"])


def serve_ffn(x, layer, cfg: SdarMoeConfig, ld=None):
    """x + experts(norm(x)), and the choice [rows, k]."""
    moe = layer["moe"]
    with jax.named_scope("norm"):
        g = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
    flat = g.reshape(-1, g.shape[-1])
    with jax.named_scope("router"):
        idx, w = expert_mod.route_softmax_top_k(
            flat, moe["router"], cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob)
    with jax.named_scope("experts"):
        y = expert_mod.expert_share(flat, idx, w, moe, range(cfg.n_experts))
        return x + y.astype(x.dtype).reshape(x.shape), idx


def serve_final_norm(x, params, cfg: SdarMoeConfig):
    with jax.named_scope("norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def serve_lm_head(x, params, cfg: SdarMoeConfig):
    """The output projection (its own matrix), float32 logits."""
    with jax.named_scope("lm_head"):
        return jnp.einsum("...d,dv->...v", x, params["lm_head"],
                          preferred_element_type=jnp.float32)
