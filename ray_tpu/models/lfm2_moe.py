"""LFM2-MoE (``model_type`` ``lfm2_moe``, e.g. LiquidAI/LFM2-8B-A1B): a
pre-norm residual block whose token mixer is, layer by layer, either a
gated short convolution or grouped-query attention, and whose feed-forward
is dense SwiGLU in the first ``n_dense`` layers and routed experts after.

Layer i, mixer kind from ``layer_types[i]``: ``h = x + mixer(norm_op(x))``,
``y = h + ffn(norm_ffn(h))``; one RMSNorm after the last layer, then the
head, tied to the embedding. RMSNorm ``w * x / sqrt(mean(x^2) + eps)``.

- ``conv``: ``[B, C, u] = split3(z W_in)`` (W_in [D, 3D], no bias);
  ``v = B * u``; ``c_t = sum_j kernel[j] * v_{t-K+1+j}`` (depthwise causal
  convolution, K = 3 taps, zeros before the sequence's start);
  ``out = (C * c) W_out``. What a sequence carries from token to token is
  its last K - 1 columns of ``v``: ``[K - 1, D]`` a conv layer.
- ``full_attention``: ``q = RoPE(rms(z W_q))``, ``k = RoPE(rms(z W_k))``
  with one RMSNorm weight of ``head_dim`` each, applied per head BEFORE the
  rotation; causal softmax attention scaled by ``head_dim ** -0.5``;
  ``W_o``. The rotation pairs lanes as ``llama.apply_rope`` does.
- routed feed-forward: ``s = sigmoid(z W_g)`` over all experts; chosen =
  the ``top_k`` largest of ``s + b``; weights ``s_e / (sum of the chosen s
  + 1e-6)`` times ``scaling``; output ``sum_e w_e SwiGLU_e(z)``. No shared
  expert; the bias selects and never weighs (parallel/expert.py).

Parameters are a LIST of layers (they differ in kind), each weight its own
array, so a program reads a layer's weights where they lie. This module is
the architecture's serving block (models/block.py has the contract).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.block import CacheSpec, LayerDef
from ray_tpu.models.llama import apply_rope, rms_norm, rope_freqs  # noqa: F401
from ray_tpu.parallel import expert as expert_mod

CONV, ATTN = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    dim: int = 2048
    layer_types: tuple = (CONV, CONV, ATTN, CONV)
    n_dense: int = 2                 # leading layers with a dense SwiGLU
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    ffn_dim: int = 7168
    n_experts: int = 32
    top_k: int = 4
    expert_dim: int = 1792
    conv_kernel: int = 3
    max_seq_len: int = 2048
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    scaling: float = 1.0             # routed_scaling_factor
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


def lfm2_moe_tiny(**kw) -> Lfm2MoeConfig:
    """Test config: every kind of layer, heads of 16, 8 experts of 32."""
    d = dict(vocab_size=512, dim=64, layer_types=(CONV, CONV, ATTN, CONV),
             n_dense=2, n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128,
             n_experts=8, top_k=2, expert_dim=32, max_seq_len=192,
             rope_theta=10000.0, dtype=jnp.float32)
    d.update(kw)
    return Lfm2MoeConfig(**d)


def _routed(cfg: Lfm2MoeConfig, i: int) -> bool:
    return i >= cfg.n_dense


def num_params(cfg: Lfm2MoeConfig) -> int:
    d, hd = cfg.dim, cfg.head_dim
    conv = d * 3 * d + cfg.conv_kernel * d + d * d
    attn = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd \
        + cfg.n_heads * hd * d + 2 * hd
    dense = 3 * d * cfg.ffn_dim
    routed = d * cfg.n_experts + cfg.n_experts \
        + 3 * cfg.n_experts * d * cfg.expert_dim
    total = cfg.vocab_size * d + d
    for i, kind in enumerate(cfg.layer_types):
        total += (conv if kind == CONV else attn) + 2 * d
        total += routed if _routed(cfg, i) else dense
    return total


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=1)
def init_params(key, cfg: Lfm2MoeConfig):
    """Normal, std 1/sqrt(fan_in), made under jit in the served dtype (no
    float32 copy of the model beside the weights); the selection bias
    float32, normal with std 0.02, so that selecting and weighing differ."""
    dt = jnp.dtype(cfg.dtype)

    def w(k, *shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    d, h, hkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(key, cfg.n_layers + 1)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(keys[i], 9)
        lp = {"op_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt)}
        if kind == CONV:
            lp["conv"] = {
                "w_in": w(k[0], d, 3 * d, fan_in=d),
                "kernel": w(k[1], cfg.conv_kernel, d, fan_in=cfg.conv_kernel),
                "w_out": w(k[2], d, d, fan_in=d)}
        else:
            lp["attn"] = {
                "wq": w(k[0], d, h, hd, fan_in=d),
                "wk": w(k[1], d, hkv, hd, fan_in=d),
                "wv": w(k[2], d, hkv, hd, fan_in=d),
                "wo": w(k[3], h, hd, d, fan_in=h * hd),
                "q_norm": jnp.ones((hd,), dt), "k_norm": jnp.ones((hd,), dt)}
        if _routed(cfg, i):
            e, f = cfg.n_experts, cfg.expert_dim
            lp["moe"] = {
                "router": w(k[7], d, e, fan_in=d),
                "bias": 0.02 * jax.random.normal(k[8], (e,), jnp.float32),
                "w_gate": w(k[4], e, d, f, fan_in=d),
                "w_up": w(k[5], e, d, f, fan_in=d),
                "w_down": w(k[6], e, f, d, fan_in=f)}
        else:
            f = cfg.ffn_dim
            lp["mlp"] = {"w_gate": w(k[4], d, f, fan_in=d),
                         "w_up": w(k[5], d, f, fan_in=d),
                         "w_down": w(k[6], f, d, fan_in=f)}
        layers.append(lp)
    return {"embed": w(keys[-1], cfg.vocab_size, d, fan_in=d),
            "layers": layers, "final_norm": jnp.ones((d,), dt)}


def load_params(path: str, cfg: Lfm2MoeConfig | None = None):
    raise NotImplementedError(
        "lfm2_moe has no checkpoint reader yet: serve it on seeded weights "
        "(checkpoint_path=None)")


_NO_TP = ("lfm2_moe has no tensor-parallel partition rules yet (slot state "
          "and experts need their own): tp_degree must be 1")


def check_tp_divides(cfg: Lfm2MoeConfig, tp: int) -> None:
    if tp != 1:
        raise ValueError(_NO_TP)


def serve_partition_rules():
    raise ValueError(_NO_TP)


# ---------------------------------------------------------------------------
# the serving block (models/block.py)
# ---------------------------------------------------------------------------

def cache_spec(cfg: Lfm2MoeConfig) -> CacheSpec:
    n_attn = sum(k == ATTN for k in cfg.layer_types)
    return CacheSpec(
        paged_layers=n_attn, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, state_layers=cfg.n_layers - n_attn,
        state_shape=(cfg.conv_kernel - 1, cfg.dim),
        routed_layers=max(0, cfg.n_layers - cfg.n_dense), top_k=cfg.top_k,
        n_experts=cfg.n_experts)


def serve_params(params, cfg: Lfm2MoeConfig):
    """As the checkpoint lays it: a head-major row of this block's heads
    of 64 would fill half a lane tile (models/block.py ``head_major``)."""
    return params


def serve_layers(cfg: Lfm2MoeConfig) -> tuple:
    out, pages, states, routed = [], 0, 0, 0
    for i, kind in enumerate(cfg.layer_types):
        r = _routed(cfg, i)
        out.append(LayerDef(
            mixer="attn" if kind == ATTN else "conv",
            ffn="routed" if r else "dense",
            page_layer=pages if kind == ATTN else -1,
            state_layer=states if kind == CONV else -1,
            routed_layer=routed if r else -1))
        pages += kind == ATTN
        states += kind == CONV
        routed += r
    return tuple(out)


def serve_embed(params, tokens, cfg: Lfm2MoeConfig):
    return params["embed"][tokens].astype(cfg.dtype)


def serve_qkv(x, layer, cos, sin, cfg: Lfm2MoeConfig):
    """Pre-mixer norm, q/k/v projections, the per-head q/k norm, RoPE."""
    a = layer["attn"]
    with jax.named_scope("norm"):
        h = rms_norm(x, layer["op_norm"], cfg.norm_eps)
    with jax.named_scope("attn"):
        q = jnp.einsum("btd,dhk->bthk", h, a["wq"])
        k = jnp.einsum("btd,dhk->bthk", h, a["wk"])
        v = jnp.einsum("btd,dhk->bthk", h, a["wv"])
        q = rms_norm(q, a["q_norm"], cfg.norm_eps)
        k = rms_norm(k, a["k_norm"], cfg.norm_eps)
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def serve_attn_out(attn, layer):
    return jnp.einsum("...hk,hkd->...d", attn, layer["attn"]["wo"])


def serve_conv(x, layer, prev, cfg: Lfm2MoeConfig):
    """The gated short convolution over x [B, T, D] after the columns
    ``prev`` [B, K-1, D] that the sequence's earlier tokens left. Returns
    (x + mixer(norm(x)), ext [B, K-1+T, D]): ``ext`` is ``prev`` followed
    by this call's columns ``v``."""
    c = layer["conv"]
    taps = cfg.conv_kernel
    with jax.named_scope("norm"):
        z = rms_norm(x, layer["op_norm"], cfg.norm_eps)
    with jax.named_scope("conv"):
        gate_b, gate_c, u = jnp.split(z @ c["w_in"], 3, axis=-1)
        ext = jnp.concatenate([prev.astype(u.dtype), gate_b * u], axis=1)
        t = x.shape[1]
        conv = sum(c["kernel"][j] * ext[:, j:j + t] for j in range(taps))
        return x + (gate_c * conv) @ c["w_out"], ext


def _swiglu(g, m):
    return (jax.nn.silu(g @ m["w_gate"]) * (g @ m["w_up"])) @ m["w_down"]


def serve_ffn(x, layer, cfg: Lfm2MoeConfig, ld: LayerDef):
    """x + ffn(norm(x)); with routed experts also the choice [rows, k]."""
    with jax.named_scope("norm"):
        g = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
    if ld.ffn == "dense":
        with jax.named_scope("mlp"):
            return x + _swiglu(g, layer["mlp"]), None
    moe = layer["moe"]
    flat = g.reshape(-1, g.shape[-1])
    with jax.named_scope("router"):
        idx, w = expert_mod.route_sigmoid_top_k(
            flat, moe["router"], moe["bias"] if cfg.use_expert_bias else None,
            cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
            scaling=cfg.scaling)
    with jax.named_scope("experts"):
        y = expert_mod.expert_share(flat, idx, w, moe, range(cfg.n_experts))
        return x + y.astype(x.dtype).reshape(x.shape), idx


def serve_final_norm(x, params, cfg: Lfm2MoeConfig):
    with jax.named_scope("norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def serve_lm_head(x, params, cfg: Lfm2MoeConfig):
    """The output projection, tied to the embedding; float32 logits."""
    with jax.named_scope("lm_head"):
        return jnp.einsum("...d,vd->...v", x, params["embed"],
                          preferred_element_type=jnp.float32)
