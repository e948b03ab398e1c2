"""JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``): the DeepSeek-V3
block. A pre-norm residual block whose mixer is multi-head LATENT attention
and whose feed-forward is dense SwiGLU in the first ``n_dense`` layers and
routed experts beside a shared expert after; one RMSNorm after the last
layer, then an untied head. RMSNorm ``w * x / sqrt(mean(x^2) + eps)``, no
bias anywhere.

Mixer, ``h = norm(x)``:
  ``c_q = norm(h W_qa)`` [q_rank]; ``q = c_q W_qb`` as H heads of
  ``[q_nope (nope_dim) | q_rope (rope_dim)]``;
  ``[c_kv (kv_rank) | k_r (rope_dim)] = h W_kva``; ``c_kv <- norm(c_kv)``;
  ``q_rope`` and ``k_r`` rotated (lanes (2i, 2i + 1) pair, as
  ``llama.apply_rope`` pairs them); ``k_r`` is ONE vector a token, shared
  by all heads. What a token leaves in the cache is ``[c_kv | k_r]``:
  ``latent_dim`` = kv_rank + rope_dim numbers a layer.
  EXPANDED (the published form): ``k_nope_h = c_kv W_uk[h]``, ``v_h = c_kv
  W_uv[h]`` (the published ``kv_b_proj`` holds the two side by side a head;
  here they are two arrays), ``k_h = [k_nope_h | k_r]``, ``a_h = softmax(q_h
  k_h^T / sqrt(nope_dim + rope_dim) + causal) v_h``, ``o = concat_h(a_h) W_o``.
  ABSORBED (the same numbers, read off the cache): ``q~_h = q_nope_h
  W_uk[h]^T`` [kv_rank], scores ``(q~_h . c_kv + q_rope_h . k_r) / sqrt(nope_dim
  + rope_dim)``, ``o~_h = sum p c_kv`` [kv_rank], ``a_h = o~_h W_uv[h]``:
  multi-query attention on one KV head whose key row's first kv_rank lanes
  are also its value row. A whole prompt runs expanded, everything that
  reads the cache absorbed (models/block.py: ``serve_latent*``).
Routed feed-forward, ``g = norm(x)``: ``s = sigmoid(g W_r)`` in float32; the
  ``top_k`` largest of ``s + bias`` chosen (the bias selects and never
  weighs; with ``n_group`` = ``topk_group`` = 1 the published group-limited
  step is the identity); weights ``s`` of the chosen over their sum
  (+ 1e-20), times ``scaling``; ``y = sum_e w_e SwiGLU_e(g)`` PLUS one shared
  SwiGLU of ``n_shared * expert_dim`` on every token, unweighted.
The published multi-token-prediction block (``num_nextn_predict_layers``) is
a training loss and a drafter; generation does not run it, nor does this.

Parameters are a LIST of layers (they differ in kind), each weight its own
array. This module is the architecture's serving block (models/block.py
has the contract).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.block import CacheSpec, LayerDef
from ray_tpu.models.llama import apply_rope, rms_norm
from ray_tpu.parallel import expert as expert_mod


@dataclasses.dataclass(frozen=True)
class JoyaiConfig:
    vocab_size: int = 129280
    dim: int = 2048
    n_layers: int = 40
    n_dense: int = 1                 # first_k_dense_replace
    n_heads: int = 32
    q_rank: int = 1536               # q_lora_rank
    kv_rank: int = 512               # kv_lora_rank
    nope_dim: int = 128              # qk_nope_head_dim
    rope_dim: int = 64               # qk_rope_head_dim
    v_dim: int = 128                 # v_head_dim
    ffn_dim: int = 7168
    n_experts: int = 256
    top_k: int = 8
    expert_dim: int = 768
    n_shared: int = 1
    max_seq_len: int = 4096
    rope_theta: float = 32000000.0
    norm_eps: float = 1e-6
    scaling: float = 2.5             # routed_scaling_factor
    norm_topk_prob: bool = True
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        """The width of a query and key head (the softmax scale's)."""
        return self.nope_dim + self.rope_dim

    @property
    def latent_dim(self) -> int:
        """Numbers a token leaves in the cache, a layer."""
        return self.kv_rank + self.rope_dim


def joyai_tiny(**kw) -> JoyaiConfig:
    """Test config: a dense layer and two routed ones, 4 heads of 16 + 8
    (values of 16), ranks 48 and 32, 16 experts of 32 top-4 + one shared."""
    d = dict(vocab_size=512, dim=64, n_layers=3, n_dense=1, n_heads=4,
             q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
             ffn_dim=128, n_experts=16, top_k=4, expert_dim=32,
             max_seq_len=192, rope_theta=10000.0, dtype=jnp.float32)
    d.update(kw)
    return JoyaiConfig(**d)


def _routed(cfg: JoyaiConfig, i: int) -> bool:
    return i >= cfg.n_dense


def num_params(cfg: JoyaiConfig) -> int:
    d, h = cfg.dim, cfg.n_heads
    attn = d * cfg.q_rank + cfg.q_rank * h * cfg.head_dim \
        + d * cfg.latent_dim + cfg.kv_rank * h * (cfg.nope_dim + cfg.v_dim) \
        + h * cfg.v_dim * d + cfg.q_rank + cfg.kv_rank + 2 * d
    routed = d * cfg.n_experts + cfg.n_experts \
        + 3 * (cfg.n_experts + cfg.n_shared) * d * cfg.expert_dim
    n_routed = max(0, cfg.n_layers - cfg.n_dense)
    return 2 * cfg.vocab_size * d + d + cfg.n_layers * attn \
        + (cfg.n_layers - n_routed) * 3 * d * cfg.ffn_dim + n_routed * routed


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _normal(key, shape: tuple, fan_in: int, dtype, stacked: bool = False):
    """One matrix, normal with std 1/sqrt(fan_in), in ``dtype``.
    ``stacked`` (a stack of experts): drawn an expert at a time, so the
    float32 draw beside it is one expert's and not the stack's (256 x
    2048 x 768: 1.6 GB)."""
    def draw(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)
    if stacked:
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(key, shape[0]))
    return draw(key, shape)


def init_params(key, cfg: JoyaiConfig):
    """Normal, std 1/sqrt(fan_in), in the served dtype, ONE MATRIX A
    PROGRAM (:func:`_normal`): a routed layer's experts are 1.2 B
    parameters, and one program for the model may hold several matrices'
    float32 draws at once beside 11 GB of weights. The selection bias
    float32, normal with std 0.02, so that selecting and weighing differ."""
    dt = jnp.dtype(cfg.dtype)

    def w(k, *shape, fan_in, stacked=False):
        return _normal(k, shape, fan_in, dt, stacked)

    d, h = cfg.dim, cfg.n_heads
    keys = jax.random.split(key, cfg.n_layers + 2)
    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i], 14)
        lp = {"attn_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt),
              "attn": {
                  "wq_a": w(k[0], d, cfg.q_rank, fan_in=d),
                  "q_norm": jnp.ones((cfg.q_rank,), dt),
                  "wq_b": w(k[1], cfg.q_rank, h, cfg.head_dim,
                            fan_in=cfg.q_rank),
                  "wkv_a": w(k[2], d, cfg.latent_dim, fan_in=d),
                  "kv_norm": jnp.ones((cfg.kv_rank,), dt),
                  "w_uk": w(k[3], cfg.kv_rank, h, cfg.nope_dim,
                            fan_in=cfg.kv_rank),
                  "w_uv": w(k[4], cfg.kv_rank, h, cfg.v_dim,
                            fan_in=cfg.kv_rank),
                  "wo": w(k[5], h, cfg.v_dim, d, fan_in=h * cfg.v_dim)}}
        if _routed(cfg, i):
            e, f, fs = cfg.n_experts, cfg.expert_dim, \
                cfg.n_shared * cfg.expert_dim
            lp["moe"] = {
                "router": w(k[6], d, e, fan_in=d),
                "bias": 0.02 * jax.random.normal(k[7], (e,), jnp.float32),
                "w_gate": w(k[8], e, d, f, fan_in=d, stacked=True),
                "w_up": w(k[9], e, d, f, fan_in=d, stacked=True),
                "w_down": w(k[10], e, f, d, fan_in=f, stacked=True),
                "shared": {"w_gate": w(k[11], d, fs, fan_in=d),
                           "w_up": w(k[12], d, fs, fan_in=d),
                           "w_down": w(k[13], fs, d, fan_in=fs)}}
        else:
            f = cfg.ffn_dim
            lp["mlp"] = {"w_gate": w(k[8], d, f, fan_in=d),
                         "w_up": w(k[9], d, f, fan_in=d),
                         "w_down": w(k[10], f, d, fan_in=f)}
        layers.append(lp)
    return {"embed": w(keys[-2], cfg.vocab_size, d, fan_in=d),
            "layers": layers, "final_norm": jnp.ones((d,), dt),
            "lm_head": w(keys[-1], d, cfg.vocab_size, fan_in=d)}


def load_params(path: str, cfg: JoyaiConfig | None = None):
    raise NotImplementedError(
        "joyai has no checkpoint reader yet: serve it on seeded weights "
        "(checkpoint_path=None)")


_NO_TP = ("a latent mixer keeps one row a token, shared by every head: one "
          "KV head cannot be split by head, and no other partition of it "
          "(or of the experts) is written yet: tp_degree must be 1")


def check_tp_divides(cfg: JoyaiConfig, tp: int) -> None:
    if tp != 1:
        raise ValueError(_NO_TP)


def serve_partition_rules():
    raise ValueError(_NO_TP)


# ---------------------------------------------------------------------------
# the serving block (models/block.py)
# ---------------------------------------------------------------------------

def cache_spec(cfg: JoyaiConfig) -> CacheSpec:
    return CacheSpec(
        paged_layers=cfg.n_layers, n_kv_heads=1, head_dim=cfg.head_dim,
        routed_layers=max(0, cfg.n_layers - cfg.n_dense), top_k=cfg.top_k,
        n_experts=cfg.n_experts, latent_dim=cfg.latent_dim,
        value_dim=cfg.kv_rank)


def serve_params(params, cfg: JoyaiConfig):
    """As the checkpoint lays it: the latent mixer's projections (3 % of
    a step, PERF.md ``latent_proj_share.joyai``) keep their layout."""
    return params


def serve_layers(cfg: JoyaiConfig) -> tuple:
    """Every layer keeps a latent row; layer i owns row i of the pool, a
    routed one row i - n_dense of the routing record."""
    return tuple(LayerDef(
        mixer="latent", ffn="routed" if _routed(cfg, i) else "dense",
        page_layer=i, routed_layer=i - cfg.n_dense if _routed(cfg, i) else -1)
        for i in range(cfg.n_layers))


def rope_freqs(cfg: JoyaiConfig, positions):
    """positions [B, T] -> (cos, sin) [B, T, rope_dim / 2], float32."""
    inv = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, cfg.rope_dim, 2, dtype=jnp.float32) / cfg.rope_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def serve_embed(params, tokens, cfg: JoyaiConfig):
    return params["embed"][tokens].astype(cfg.dtype)


def _queries(h, a, cos, sin, cfg: JoyaiConfig):
    """(q_nope [B, T, H, nope_dim], q_rope [B, T, H, rope_dim] rotated)."""
    with jax.named_scope("q_proj"):
        c_q = rms_norm(h @ a["wq_a"], a["q_norm"], cfg.norm_eps)
        q = jnp.einsum("btr,rhk->bthk", c_q, a["wq_b"])
        return q[..., :cfg.nope_dim], apply_rope(q[..., cfg.nope_dim:],
                                                 cos, sin)


def _latent(h, a, cos, sin, cfg: JoyaiConfig):
    """(c_kv [B, T, kv_rank] normed, k_r [B, T, rope_dim] rotated)."""
    with jax.named_scope("kv_latent"):
        ckr = h @ a["wkv_a"]
        c_kv = rms_norm(ckr[..., :cfg.kv_rank], a["kv_norm"], cfg.norm_eps)
        k_r = apply_rope(ckr[..., None, cfg.kv_rank:], cos, sin)[..., 0, :]
        return c_kv, k_r


def _projections(x, layer, cos, sin, cfg: JoyaiConfig):
    """What both forms of the mixer start from: (q_nope, q_rope, c_kv,
    k_r) of the normed x."""
    with jax.named_scope("norm"):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    return (*_queries(h, layer["attn"], cos, sin, cfg),
            *_latent(h, layer["attn"], cos, sin, cfg))


def serve_latent(x, layer, cos, sin, cfg: JoyaiConfig):
    """The absorbed form: (q [B, T, H, latent_dim], entry [B, T,
    latent_dim])."""
    q_nope, q_rope, c_kv, k_r = _projections(x, layer, cos, sin, cfg)
    with jax.named_scope("absorb"):
        q_abs = jnp.einsum("bthn,rhn->bthr", q_nope, layer["attn"]["w_uk"])
    return (jnp.concatenate([q_abs, q_rope], axis=-1),
            jnp.concatenate([c_kv, k_r], axis=-1))


def serve_latent_out(o, layer):
    a = layer["attn"]
    with jax.named_scope("absorb"):
        heads = jnp.einsum("...hr,rhv->...hv", o, a["w_uv"])
    with jax.named_scope("attn"):
        return serve_attn_out(heads, layer)


def serve_latent_expanded(x, layer, cos, sin, cfg: JoyaiConfig):
    """The published form: (q, k [B, T, H, nope_dim + rope_dim], v [B, T,
    H, v_dim], entry [B, T, latent_dim])."""
    a = layer["attn"]
    q_nope, q_rope, c_kv, k_r = _projections(x, layer, cos, sin, cfg)
    with jax.named_scope("attn"):
        k_nope = jnp.einsum("btr,rhn->bthn", c_kv, a["w_uk"])
        v = jnp.einsum("btr,rhv->bthv", c_kv, a["w_uv"])
        k_rope = jnp.broadcast_to(k_r[:, :, None, :],
                                  k_nope.shape[:3] + (cfg.rope_dim,))
    return (jnp.concatenate([q_nope, q_rope], axis=-1),
            jnp.concatenate([k_nope, k_rope], axis=-1), v,
            jnp.concatenate([c_kv, k_r], axis=-1))


def serve_attn_out(attn, layer):
    return jnp.einsum("...hv,hvd->...d", attn, layer["attn"]["wo"])


def _swiglu(g, m):
    return (jax.nn.silu(g @ m["w_gate"]) * (g @ m["w_up"])) @ m["w_down"]


def serve_ffn(x, layer, cfg: JoyaiConfig, ld: LayerDef):
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
            flat, moe["router"], moe["bias"], cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob, scaling=cfg.scaling,
            norm_eps=1e-20)
    with jax.named_scope("experts"):
        y = expert_mod.expert_share(flat, idx, w, moe, range(cfg.n_experts))
    with jax.named_scope("shared_expert"):
        y = y.astype(x.dtype) + _swiglu(flat, moe["shared"])
    return x + y.reshape(x.shape), idx


def serve_final_norm(x, params, cfg: JoyaiConfig):
    with jax.named_scope("norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def serve_lm_head(x, params, cfg: JoyaiConfig):
    """The output projection (its own matrix), float32 logits."""
    with jax.named_scope("lm_head"):
        return jnp.einsum("...d,dv->...v", x, params["lm_head"],
                          preferred_element_type=jnp.float32)
