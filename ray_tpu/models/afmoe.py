"""Trinity-Large (``model_type`` ``afmoe``): GQA attention under a per-lane
sigmoid gate, WINDOW layers beside full ones, four norms a layer, and
routed experts beside a shared one after the leading dense layers. RMSNorm
``w * x / sqrt(mean(x^2) + eps)``, no bias anywhere; one RMSNorm after the
last layer, then an untied head.

``x0 = embed[token] * sqrt(dim)`` (``mup``). A layer, with norms n1..n4:
  ``a = n1(x)``; ``q = a Wq`` [H x hd], ``k = a Wk``, ``v = a Wv`` [Hkv x
  hd], ``g = sigmoid(a Wg)`` [H x hd]; q and k RMS-normed over the hd lanes
  of each head (one weight a layer each).
  A WINDOW layer (every layer but each ``global_every``-th: ``(l + 1) %
  global_every != 0``) rotates q and k (lanes (2i, 2i + 1) pair, as
  ``llama.apply_rope`` pairs them) and query i sees key j iff ``0 <= i - j <
  window``; a FULL layer rotates nothing and sees every ``j <= i``.
  ``o = softmax(q k^T / sqrt(hd)) v`` (kv-major GQA);
  ``x <- x + n2((o * g) Wo)``.
  ``b = n3(x)``; a dense layer (``l < n_dense``) ``f = SwiGLU(b)`` of width
  ``ffn_dim``; a routed one ``s = sigmoid(b Wr)`` in float32 over ALL
  ``n_experts``, the ``top_k`` largest of ``s + bias`` chosen (the bias
  selects and never weighs), weights ``s`` of the chosen over their sum
  (+ 1e-20), times ``scaling``; ``f = SwiGLU_shared(b) + sum_e w_e
  SwiGLU_e(b)``; ``x <- x + n4(f)``.

ONE CHIP'S SHARE OF THE EXPERTS: the parameters hold the first
``experts_held`` experts' matrices only; the router scores all
``n_experts`` and ``parallel/expert.expert_share`` computes the picks that
land on the held range (a pick of an expert held elsewhere adds nothing
here: the chips of a deployment add their shares up). Attention, the shared
expert, the router and the norms are whole on every chip.

Parameters are a LIST of layers (they differ in kind), each weight its own
array. This module is the architecture's serving block (models/block.py
has the contract; its mixer kind is "gated").
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.block import CacheSpec, LayerDef, head_major
from ray_tpu.models.joyai import _normal
from ray_tpu.models.llama import apply_rope, rms_norm
from ray_tpu.parallel import expert as expert_mod


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    dim: int = 3072
    n_layers: int = 60
    n_dense: int = 6                 # num_dense_layers
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn_dim: int = 12288
    n_experts: int = 256
    experts_held: int = 256          # this chip's share: the first of them
    top_k: int = 4
    expert_dim: int = 3072           # moe_intermediate_size
    n_shared: int = 1
    window: int = 4096               # sliding_window
    global_every: int = 4            # global_attn_every_n_layers
    max_seq_len: int = 16384
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    scaling: float = 2.448           # route_scale
    mup: bool = True                 # mup_enabled: embed * sqrt(dim)
    dtype: Any = jnp.bfloat16


def afmoe_tiny(**kw) -> AfmoeConfig:
    """Test config: a dense window layer, two routed window layers and a
    routed full one; 4 heads on 2 KV heads of 16, window 16, 16 experts of
    32 top-2 (all held) + one shared."""
    d = dict(vocab_size=512, dim=64, n_layers=4, n_dense=1, n_heads=4,
             n_kv_heads=2, head_dim=16, ffn_dim=128, n_experts=16,
             experts_held=16, top_k=2, expert_dim=32, window=16,
             max_seq_len=192, dtype=jnp.float32)
    d.update(kw)
    return AfmoeConfig(**d)


def _routed(cfg: AfmoeConfig, i: int) -> bool:
    return i >= cfg.n_dense


def window_of(cfg: AfmoeConfig, i: int) -> int:
    """Layer i's window: 0 where it is a full layer."""
    return 0 if (i + 1) % cfg.global_every == 0 else cfg.window


def num_params(cfg: AfmoeConfig) -> int:
    """Of what this chip holds (``experts_held`` of the experts)."""
    d, hd = cfg.dim, cfg.head_dim
    attn = d * hd * (3 * cfg.n_heads + 2 * cfg.n_kv_heads) + 2 * hd + 4 * d
    routed = d * cfg.n_experts + cfg.n_experts \
        + 3 * (cfg.experts_held + cfg.n_shared) * d * cfg.expert_dim
    n_routed = max(0, cfg.n_layers - cfg.n_dense)
    return 2 * cfg.vocab_size * d + d + cfg.n_layers * attn \
        + (cfg.n_layers - n_routed) * 3 * d * cfg.ffn_dim + n_routed * routed


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_params(key, cfg: AfmoeConfig):
    """Normal, std 1/sqrt(fan_in), in the served dtype, ONE MATRIX A
    PROGRAM (``joyai._normal``: a stack of experts an expert at a time).
    The selection bias float32, normal with std 0.02, so that selecting
    and weighing differ. Only the held experts' matrices are made."""
    dt = jnp.dtype(cfg.dtype)

    def w(k, *shape, fan_in, stacked=False):
        return _normal(k, shape, fan_in, dt, stacked)

    d, h, hkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(key, cfg.n_layers + 2)
    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i], 13)
        lp = {name: jnp.ones((d,), dt) for name in (
            "attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm")}
        lp["attn"] = {"wq": w(k[0], d, h, hd, fan_in=d),
                      "wk": w(k[1], d, hkv, hd, fan_in=d),
                      "wv": w(k[2], d, hkv, hd, fan_in=d),
                      "wg": w(k[3], d, h, hd, fan_in=d),
                      "wo": w(k[4], h, hd, d, fan_in=h * hd),
                      "q_norm": jnp.ones((hd,), dt),
                      "k_norm": jnp.ones((hd,), dt)}
        if _routed(cfg, i):
            e, f, fs = cfg.experts_held, cfg.expert_dim, \
                cfg.n_shared * cfg.expert_dim
            lp["moe"] = {
                "router": w(k[5], d, cfg.n_experts, fan_in=d),
                "bias": 0.02 * jax.random.normal(
                    k[6], (cfg.n_experts,), jnp.float32),
                "w_gate": w(k[7], e, d, f, fan_in=d, stacked=True),
                "w_up": w(k[8], e, d, f, fan_in=d, stacked=True),
                "w_down": w(k[9], e, f, d, fan_in=f, stacked=True),
                "shared": {"w_gate": w(k[10], d, fs, fan_in=d),
                           "w_up": w(k[11], d, fs, fan_in=d),
                           "w_down": w(k[12], fs, d, fan_in=fs)}}
        else:
            f = cfg.ffn_dim
            lp["mlp"] = {"w_gate": w(k[7], d, f, fan_in=d),
                         "w_up": w(k[8], d, f, fan_in=d),
                         "w_down": w(k[9], f, d, fan_in=f)}
        layers.append(lp)
    return {"embed": w(keys[-2], cfg.vocab_size, d, fan_in=d),
            "layers": layers, "final_norm": jnp.ones((d,), dt),
            "lm_head": w(keys[-1], d, cfg.vocab_size, fan_in=d)}


def load_params(path: str, cfg: AfmoeConfig | None = None):
    raise NotImplementedError(
        "afmoe has no checkpoint reader yet: serve it on seeded weights "
        "(checkpoint_path=None)")


_NO_TP = ("window layers keep a ring of pages a slot, and no partition of "
          "the two pools (or of the experts inside one replica) is written "
          "yet: tp_degree must be 1")


def check_tp_divides(cfg: AfmoeConfig, tp: int) -> None:
    if tp != 1:
        raise ValueError(_NO_TP)


def serve_partition_rules():
    raise ValueError(_NO_TP)


# ---------------------------------------------------------------------------
# the serving block (models/block.py)
# ---------------------------------------------------------------------------

def serve_layers(cfg: AfmoeConfig) -> tuple:
    """Layer i's window, and its row of ITS pool: window layers count
    their own rows (the ring pool's), full layers theirs."""
    out, rows = [], {True: 0, False: 0}
    for i in range(cfg.n_layers):
        win = window_of(cfg, i)
        out.append(LayerDef(
            mixer="gated", ffn="routed" if _routed(cfg, i) else "dense",
            page_layer=rows[win > 0], window=win,
            routed_layer=i - cfg.n_dense if _routed(cfg, i) else -1))
        rows[win > 0] += 1
    return tuple(out)


def cache_spec(cfg: AfmoeConfig) -> CacheSpec:
    windows = [window_of(cfg, i) > 0 for i in range(cfg.n_layers)]
    return CacheSpec(
        paged_layers=windows.count(False), n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        routed_layers=max(0, cfg.n_layers - cfg.n_dense), top_k=cfg.top_k,
        n_experts=cfg.experts_held, window=cfg.window,
        window_layers=windows.count(True))


def rope_freqs(cfg: AfmoeConfig, positions):
    """positions [B, T] -> (cos, sin) [B, T, head_dim / 2], float32."""
    inv = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, cfg.head_dim, 2, dtype=jnp.float32) / cfg.head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def serve_params(params, cfg: AfmoeConfig):
    """wq, wk, wv and wg of every layer head-major, [H, D, hd] (``wq_hm``
    ...; models/block.py ``head_major``)."""
    return {**params, "layers": [
        {**lp, "attn": head_major(lp["attn"], ("wq", "wk", "wv", "wg"))}
        for lp in params["layers"]]}


def serve_embed(params, tokens, cfg: AfmoeConfig):
    x = params["embed"][tokens]
    if cfg.mup:
        x = x.astype(jnp.float32) * cfg.dim ** 0.5
    return x.astype(cfg.dtype)


def serve_gated_qkv(x, layer, cos, sin, cfg: AfmoeConfig, ld: LayerDef):
    """(q [B, T, H, hd], k, v [B, T, Hkv, hd], gate [B, T, H, hd]) of the
    normed x; q and k normed a head, and rotated in a window layer only."""
    a = layer["attn"]
    with jax.named_scope("norm"):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    with jax.named_scope("attn"):
        q = jnp.einsum("btd,hdk->bthk", h, a["wq_hm"])
        k = jnp.einsum("btd,hdk->bthk", h, a["wk_hm"])
        v = jnp.einsum("btd,hdk->bthk", h, a["wv_hm"])
        q = rms_norm(q, a["q_norm"], cfg.norm_eps)
        k = rms_norm(k, a["k_norm"], cfg.norm_eps)
        if ld.window:
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    with jax.named_scope("gate"):
        gate = jax.nn.sigmoid(jnp.einsum(
            "btd,hdk->bthk", h, a["wg_hm"],
            preferred_element_type=jnp.float32)).astype(x.dtype)
    return q, k, v, gate


def serve_gated_out(attn, gate, layer, cfg: AfmoeConfig):
    """n2((attn * gate) Wo): the mixer's output, before the residual."""
    with jax.named_scope("gate"):
        o = attn * gate
    o = jnp.einsum("...hk,hkd->...d", o, layer["attn"]["wo"])
    with jax.named_scope("norm"):
        return rms_norm(o, layer["attn_post_norm"], cfg.norm_eps)


def _swiglu(g, m):
    return (jax.nn.silu(g @ m["w_gate"]) * (g @ m["w_up"])) @ m["w_down"]


def routed_parts(flat, moe, cfg: AfmoeConfig):
    """A routed layer's sum before ``n4``, of the normed rows ``flat``
    [rows, D], in its two parts: THIS CHIP'S SHARE of the routed experts
    (float32; the chips of a deployment add theirs up) and the shared
    expert (whole on every chip, counted once); and the choice [rows, k]
    over ALL experts (a pick of one held elsewhere is recorded too)."""
    with jax.named_scope("router"):
        idx, w = expert_mod.route_sigmoid_top_k(
            flat, moe["router"], moe["bias"], cfg.top_k,
            norm_topk_prob=True, scaling=cfg.scaling, norm_eps=1e-20)
    with jax.named_scope("experts"):
        share = expert_mod.expert_share(flat, idx, w, moe,
                                        range(cfg.experts_held))
    with jax.named_scope("shared_expert"):
        shared = _swiglu(flat, moe["shared"])
    return share, shared, idx


def serve_ffn(x, layer, cfg: AfmoeConfig, ld: LayerDef):
    """x + n4(ffn(n3(x))); with routed experts also the choice."""
    with jax.named_scope("norm"):
        g = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
    idx = None
    if ld.ffn == "dense":
        with jax.named_scope("mlp"):
            y = _swiglu(g, layer["mlp"])
    else:
        share, shared, idx = routed_parts(
            g.reshape(-1, g.shape[-1]), layer["moe"], cfg)
        y = (share.astype(x.dtype) + shared).reshape(x.shape)
    with jax.named_scope("norm"):
        return x + rms_norm(y, layer["ffn_post_norm"], cfg.norm_eps), idx


def serve_final_norm(x, params, cfg: AfmoeConfig):
    with jax.named_scope("norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def serve_lm_head(x, params, cfg: AfmoeConfig):
    """The output projection (its own matrix), float32 logits."""
    with jax.named_scope("lm_head"):
        return jnp.einsum("...d,dv->...v", x, params["lm_head"],
                          preferred_element_type=jnp.float32)
