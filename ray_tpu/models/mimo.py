"""MiMo-V2-Flash (``model_type`` ``mimo_v2_flash``): GQA attention whose
query and key heads are WIDER than its value heads (192 lanes against 128),
WINDOW layers beside full ones that differ in more than the window (their
KV-head count, their rotation's theta, a learned SINK in their softmax),
and routed experts with no shared one after a leading dense layer. RMSNorm
``w * x / sqrt(mean(x^2) + eps)``, no bias anywhere; one RMSNorm after the
last layer, then an untied head.

A layer, with norms n1, n2: ``a = n1(x)``; ``q = a Wq`` [H x hd], ``k = a
Wk`` [Hkv x hd], ``v = value_scale * a Wv`` [Hkv x vd]; ``Hkv`` is
``n_kv_heads`` in a FULL layer (``pattern[l]`` 0) and ``window_kv_heads`` in
a WINDOW layer (``pattern[l]`` 1). The first ``rotary_dim`` lanes of every q
and k head are rotated (lanes (2i, 2i + 1) pair, as ``llama.apply_rope``
pairs them), by ``rope_theta`` in a full layer and ``window_rope_theta`` in
a window layer; the other lanes pass as they are. Scores ``q k^T /
sqrt(hd)``; a full layer sees every ``j <= i``, a window layer ``0 <= i - j
< window``; a window layer's softmax has one more column, the learned
float32 logit ``sink[h]`` of its query head, which weighs no value (a row's
weights sum to less than 1); ``x <- x + (softmax(..) v) Wo`` (kv-major GQA).
``b = n2(x)``; a dense layer (``moe_freq[l]`` 0) ``f = SwiGLU(b)`` of width
``ffn_dim``; a routed one ``s = sigmoid(b Wr)`` in float32 over ALL
``n_experts``, the ``top_k`` largest of ``s + bias`` chosen (the bias
selects and never weighs), weights ``s`` of the chosen over their sum
(+ 1e-20); ``f = sum_e w_e SwiGLU_e(b)``; ``x <- x + f``.

ONE CHIP'S SHARE OF THE EXPERTS: the parameters hold the first
``experts_held`` experts' matrices only; the router scores all
``n_experts`` and ``parallel/expert.expert_share`` computes the picks that
land on the held range (a pick of an expert held elsewhere adds nothing
here: the chips of a deployment add their shares up). Attention, the
router and the norms are whole on every chip.

Parameters are a LIST of layers (they differ in kind), each weight its own
array; a window layer's ``attn`` holds ``sink`` [H] float32. This module is
the architecture's serving block (models/block.py has the contract; its
mixer kind is "sink", its cache spec states ``value_dim`` and
``window_kv_heads``).
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

# the seeded sinks: normal around SINK_MEAN (the published ones are learned).
# A window row's scores have unit variance (weights of std 1/sqrt(fan_in)),
# so a full window's keys sum to about 128 x e^0.5 = 210 and a sink of e^4 =
# 55 holds about a fifth of the row
SINK_MEAN, SINK_STD = 4.0, 1.0


@dataclasses.dataclass(frozen=True)
class MimoConfig:
    vocab_size: int = 152576
    dim: int = 4096
    n_layers: int = 48
    n_heads: int = 64
    n_kv_heads: int = 4              # a full layer's
    window_kv_heads: int = 8         # swa_num_key_value_heads
    head_dim: int = 192              # q and k
    value_dim: int = 128             # v_head_dim
    rotary_dim: int = 64             # int(head_dim * partial_rotary_factor)
    ffn_dim: int = 16384
    n_experts: int = 256
    experts_held: int = 256          # this chip's share: the first of them
    top_k: int = 8
    expert_dim: int = 2048           # moe_intermediate_size
    window: int = 128                # sliding_window
    # hybrid_layer_pattern (1: a window layer) and moe_layer_freq (1: routed)
    pattern: tuple = tuple(int(l != 0 and (l + 1) % 6 != 0)
                           for l in range(48))
    moe_freq: tuple = (0,) + (1,) * 47
    max_seq_len: int = 16384
    rope_theta: float = 5000000.0
    window_rope_theta: float = 10000.0   # swa_rope_theta
    norm_eps: float = 1e-5
    value_scale: float = 0.707       # attention_value_scale
    dtype: Any = jnp.bfloat16


def mimo_tiny(**kw) -> MimoConfig:
    """Test config: a dense full layer, three routed window layers and a
    routed full one; 8 heads of 24 (8 lanes rotated) on values of 16, 2 KV
    heads in a full layer and 4 in a window layer, window 8 (ONE page of
    the tests' engines), 16 experts of 32 top-2 (all held)."""
    d = dict(vocab_size=512, dim=64, n_layers=5, n_heads=8, n_kv_heads=2,
             window_kv_heads=4, head_dim=24, value_dim=16, rotary_dim=8,
             ffn_dim=128, n_experts=16, experts_held=16, top_k=2,
             expert_dim=32, window=8, pattern=(0, 1, 1, 1, 0),
             moe_freq=(0, 1, 1, 1, 1), max_seq_len=192, dtype=jnp.float32)
    d.update(kw)
    return MimoConfig(**d)


def window_of(cfg: MimoConfig, i: int) -> int:
    """Layer i's window: 0 where it is a full layer."""
    return cfg.window if cfg.pattern[i] else 0


def _kv_heads(cfg: MimoConfig, i: int) -> int:
    return cfg.window_kv_heads if cfg.pattern[i] else cfg.n_kv_heads


def _sinks(cfg: MimoConfig, i: int) -> bool:
    """A window layer's softmax has the sink, a full layer's has none
    (add_swa_attention_sink_bias / add_full_attention_sink_bias)."""
    return bool(cfg.pattern[i])


def _check(cfg: MimoConfig) -> None:
    if len(cfg.pattern) < cfg.n_layers or len(cfg.moe_freq) < cfg.n_layers:
        raise ValueError(
            f"pattern and moe_freq state a kind for {len(cfg.pattern)} and "
            f"{len(cfg.moe_freq)} layers, the model has {cfg.n_layers}")


def num_params(cfg: MimoConfig) -> int:
    """Of what this chip holds (``experts_held`` of the experts)."""
    _check(cfg)
    d, h = cfg.dim, cfg.n_heads
    total = 2 * cfg.vocab_size * d + d
    for i in range(cfg.n_layers):
        hkv = _kv_heads(cfg, i)
        total += d * h * cfg.head_dim + d * hkv * (cfg.head_dim
                                                   + cfg.value_dim) \
            + h * cfg.value_dim * d + 2 * d + (h if _sinks(cfg, i) else 0)
        total += d * cfg.n_experts + cfg.n_experts \
            + 3 * cfg.experts_held * d * cfg.expert_dim \
            if cfg.moe_freq[i] else 3 * d * cfg.ffn_dim
    return total


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_params(key, cfg: MimoConfig):
    """Normal, std 1/sqrt(fan_in), in the served dtype, ONE MATRIX A
    PROGRAM (``joyai._normal``: a stack of experts an expert at a time).
    The selection bias float32, normal with std 0.02, so that selecting
    and weighing differ; a sink float32, normal around ``SINK_MEAN``. Only
    the held experts' matrices are made."""
    _check(cfg)
    dt = jnp.dtype(cfg.dtype)

    def w(k, *shape, fan_in, stacked=False):
        return _normal(k, shape, fan_in, dt, stacked)

    d, h, hd, vd = cfg.dim, cfg.n_heads, cfg.head_dim, cfg.value_dim
    keys = jax.random.split(key, cfg.n_layers + 2)
    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i], 10)
        hkv = _kv_heads(cfg, i)
        lp = {"attn_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt),
              "attn": {"wq": w(k[0], d, h, hd, fan_in=d),
                       "wk": w(k[1], d, hkv, hd, fan_in=d),
                       "wv": w(k[2], d, hkv, vd, fan_in=d),
                       "wo": w(k[3], h, vd, d, fan_in=h * vd)}}
        if _sinks(cfg, i):
            lp["attn"]["sink"] = SINK_MEAN + SINK_STD * jax.random.normal(
                k[4], (h,), jnp.float32)
        if cfg.moe_freq[i]:
            e, f = cfg.experts_held, cfg.expert_dim
            lp["moe"] = {
                "router": w(k[5], d, cfg.n_experts, fan_in=d),
                "bias": 0.02 * jax.random.normal(
                    k[6], (cfg.n_experts,), jnp.float32),
                "w_gate": w(k[7], e, d, f, fan_in=d, stacked=True),
                "w_up": w(k[8], e, d, f, fan_in=d, stacked=True),
                "w_down": w(k[9], e, f, d, fan_in=f, stacked=True)}
        else:
            f = cfg.ffn_dim
            lp["mlp"] = {"w_gate": w(k[7], d, f, fan_in=d),
                         "w_up": w(k[8], d, f, fan_in=d),
                         "w_down": w(k[9], f, d, fan_in=f)}
        layers.append(lp)
    return {"embed": w(keys[-2], cfg.vocab_size, d, fan_in=d),
            "layers": layers, "final_norm": jnp.ones((d,), dt),
            "lm_head": w(keys[-1], d, cfg.vocab_size, fan_in=d)}


def load_params(path: str, cfg: MimoConfig | None = None):
    raise NotImplementedError(
        "mimo_v2_flash has no checkpoint reader yet: serve it on seeded "
        "weights (checkpoint_path=None)")


_NO_TP = ("window layers keep a ring of pages a slot and another number of "
          "KV heads than the full layers, and no partition of the two pools "
          "(or of the experts inside one replica) is written yet: tp_degree "
          "must be 1")


def check_tp_divides(cfg: MimoConfig, tp: int) -> None:
    if tp != 1:
        raise ValueError(_NO_TP)


def serve_partition_rules():
    raise ValueError(_NO_TP)


# ---------------------------------------------------------------------------
# the serving block (models/block.py)
# ---------------------------------------------------------------------------

def serve_layers(cfg: MimoConfig) -> tuple:
    """Layer i's window and sink, and its row of ITS pool: window layers
    count their own rows (the ring pool's), full layers theirs."""
    _check(cfg)
    out, rows, routed = [], {True: 0, False: 0}, 0
    for i in range(cfg.n_layers):
        win = window_of(cfg, i)
        out.append(LayerDef(
            mixer="sink", ffn="routed" if cfg.moe_freq[i] else "dense",
            page_layer=rows[win > 0], window=win, sink=_sinks(cfg, i),
            routed_layer=routed if cfg.moe_freq[i] else -1))
        rows[win > 0] += 1
        routed += bool(cfg.moe_freq[i])
    return tuple(out)


def cache_spec(cfg: MimoConfig) -> CacheSpec:
    windows = [bool(p) for p in cfg.pattern[:cfg.n_layers]]
    return CacheSpec(
        paged_layers=windows.count(False), n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, value_dim=cfg.value_dim,
        routed_layers=sum(map(bool, cfg.moe_freq[:cfg.n_layers])),
        top_k=cfg.top_k, n_experts=cfg.experts_held, window=cfg.window,
        window_layers=windows.count(True),
        window_kv_heads=cfg.window_kv_heads)


def rope_freqs(cfg: MimoConfig, positions):
    """positions [B, T] -> (cos, sin), each a PAIR (a full layer's, a
    window layer's) of [B, T, rotary_dim / 2], float32."""
    def of(theta):
        inv = 1.0 / (theta ** (jnp.arange(
            0, cfg.rotary_dim, 2, dtype=jnp.float32) / cfg.rotary_dim))
        ang = positions.astype(jnp.float32)[..., None] * inv
        return jnp.cos(ang), jnp.sin(ang)

    (cf, sf), (cw, sw) = of(cfg.rope_theta), of(cfg.window_rope_theta)
    return (cf, cw), (sf, sw)


def serve_params(params, cfg: MimoConfig):
    """wq, wk and wv of every layer head-major, [H, D, hd] (``wq_hm`` ...;
    models/block.py ``head_major``)."""
    return {**params, "layers": [
        {**lp, "attn": head_major(lp["attn"], ("wq", "wk", "wv"))}
        for lp in params["layers"]]}


def serve_embed(params, tokens, cfg: MimoConfig):
    return params["embed"][tokens].astype(cfg.dtype)


def _rotate(x, cos, sin, lanes: int):
    """The first ``lanes`` lanes of every head of x [B, T, H, hd] rotated,
    the others as they are."""
    return jnp.concatenate(
        [apply_rope(x[..., :lanes], cos, sin), x[..., lanes:]], axis=-1)


def serve_sink_qkv(x, layer, cos, sin, cfg: MimoConfig, ld: LayerDef):
    """(q [B, T, H, hd], k [B, T, Hkv, hd], v [B, T, Hkv, vd], sink [H] |
    None) of the normed x: the layer kind's KV heads (the weights' own),
    its theta, the values scaled, and the layer's sinks where it has them."""
    a = layer["attn"]
    kind = int(ld.window > 0)
    with jax.named_scope("norm"):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    with jax.named_scope("attn"):
        q = jnp.einsum("btd,hdk->bthk", h, a["wq_hm"])
        k = jnp.einsum("btd,hdk->bthk", h, a["wk_hm"])
        v = jnp.einsum("btd,hdk->bthk", h, a["wv_hm"]) * cfg.value_scale
        q = _rotate(q, cos[kind], sin[kind], cfg.rotary_dim)
        k = _rotate(k, cos[kind], sin[kind], cfg.rotary_dim)
    return q, k, v.astype(x.dtype), a["sink"] if ld.sink else None


def serve_attn_out(attn, layer):
    """attn [..., H, vd] through the output projection."""
    return jnp.einsum("...hk,hkd->...d", attn, layer["attn"]["wo"])


def _swiglu(g, m):
    return (jax.nn.silu(g @ m["w_gate"]) * (g @ m["w_up"])) @ m["w_down"]


def routed_parts(flat, moe, cfg: MimoConfig):
    """THIS CHIP'S SHARE of a routed layer's sum, of the normed rows
    ``flat`` [rows, D] (float32; the chips of a deployment add theirs up:
    there is no shared expert to count once), and the choice [rows, k] over
    ALL experts (a pick of one held elsewhere is recorded too)."""
    with jax.named_scope("router"):
        idx, w = expert_mod.route_sigmoid_top_k(
            flat, moe["router"], moe["bias"], cfg.top_k,
            norm_topk_prob=True, norm_eps=1e-20)
    with jax.named_scope("experts"):
        share = expert_mod.expert_share(flat, idx, w, moe,
                                        range(cfg.experts_held))
    return share, idx


def serve_ffn(x, layer, cfg: MimoConfig, ld: LayerDef):
    """x + ffn(n2(x)); with routed experts also the choice."""
    with jax.named_scope("norm"):
        g = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
    if ld.ffn == "dense":
        with jax.named_scope("mlp"):
            return x + _swiglu(g, layer["mlp"]), None
    share, idx = routed_parts(g.reshape(-1, g.shape[-1]), layer["moe"], cfg)
    return x + share.astype(x.dtype).reshape(x.shape), idx


def serve_final_norm(x, params, cfg: MimoConfig):
    with jax.named_scope("norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def serve_lm_head(x, params, cfg: MimoConfig):
    """The output projection (its own matrix), float32 logits."""
    with jax.named_scope("lm_head"):
        return jnp.einsum("...d,dv->...v", x, params["lm_head"],
                          preferred_element_type=jnp.float32)
