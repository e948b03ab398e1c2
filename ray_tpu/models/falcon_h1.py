"""Falcon-H1 (``model_type`` ``falcon_h1``, e.g. tiiuae/Falcon-H1-34B-
Instruct): a pre-norm residual block that runs TWO mixers on one input,
a Mamba-2 state-space mixer and grouped-query attention, side by side in
every layer, a dense SwiGLU after them, and fixed multipliers (muP) on
every branch. h the width, eps ``norm_eps``:

    x   = E[token] * embedding_multiplier
    u   = rms(x, in_norm)
    x   = x + ssm_out_multiplier * Mamba(u)
            + attention_out_multiplier * Attn(u * attention_in_multiplier)
    v   = rms(x, ffn_norm)
    x   = x + mlp_multipliers[1] * W_down(W_up v * silu(mlp_multipliers[0]
                                                        * W_gate v))
    logits = lm_head_multiplier * W_head rms(x, final_norm)      (untied)

- ``Attn``: q = W_q a, k = key_multiplier * W_k a, v = W_v a, no bias;
  rotary over all lanes of q and k (pairs (2i, 2i + 1), ``rope_theta``);
  causal softmax at ``head_dim ** -0.5``, query head j reads KV head
  ``j // (H / Hkv)``; W_o. The cache holds the multiplied, rotated keys.
- ``Mamba`` (I = ``ssm_heads`` x ``ssm_head_dim``, G groups, N state
  columns, K taps): ``p = W_in (ssm_in_multiplier * u)`` [I + (I + 2GN) +
  heads], times ``ssm_multipliers`` laid over the five segments z | x | B |
  C | dt; ``xBC`` through a depthwise causal convolution of K taps with a
  bias, then silu; ``dt = softplus(dt + dt_bias)``, ``A = -exp(a_log)``;
  the recurrence of ops/ssm.py a head (``S`` [heads, N, P] float32; head h
  reads group ``h // (heads / G)``); ``y += D * x``; gate FIRST
  (``y * silu(z)``), then an RMS norm over each of the G groups of I / G
  lanes, times ``norm``; ``W_out``.

What a sequence keeps between calls, a layer: K and V pages, the last K - 1
columns of ``xBC`` BEFORE the convolution (the activations' dtype) and ``S``
(float32: a state rounded at every step accumulates what a key written once
does not). Every layer is alike, but the layers are a LIST: the paged
programs' scan over stacked layers carries the two page pools and nothing
else (serve/llm/kv_cache.py ``_over_layers``), and a layer's state arrays
are its own. This module is the architecture's serving block (models/
block.py has the contract; the mixer kind is "hybrid").
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.block import CacheSpec, LayerDef, head_major
from ray_tpu.models.llama import apply_rope, rms_norm, rope_freqs  # noqa: F401


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    dim: int = 5120
    n_layers: int = 72
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    ffn_dim: int = 21504
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_state: int = 256
    ssm_groups: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 128
    max_seq_len: int = 2048
    rope_theta: float = 1e11
    norm_eps: float = 1e-5
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # z | x | B | C | dt
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    dtype: Any = jnp.bfloat16

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x, B and C side by side."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def segments(self) -> tuple:
        """Widths of W_in's five column segments, z | x | B | C | dt."""
        gn = self.ssm_groups * self.ssm_state
        return (self.ssm_inner, self.ssm_inner, gn, gn, self.ssm_heads)


def falcon_h1_tiny(**kw) -> FalconH1Config:
    """Test config: five query heads a KV head, 4 state heads of 16 in 2
    groups, 16 state columns, the multipliers as published."""
    d = dict(vocab_size=512, dim=64, n_layers=4, n_heads=10, n_kv_heads=2,
             head_dim=16, ffn_dim=128, ssm_heads=4, ssm_head_dim=16,
             ssm_state=16, ssm_groups=2, ssm_conv=4, ssm_chunk=8,
             max_seq_len=192, rope_theta=10000.0, dtype=jnp.float32)
    d.update(kw)
    return FalconH1Config(**d)


def num_params(cfg: FalconH1Config) -> int:
    d, hd = cfg.dim, cfg.head_dim
    ssm = d * sum(cfg.segments) + cfg.ssm_conv * cfg.conv_dim + cfg.conv_dim \
        + 3 * cfg.ssm_heads + cfg.ssm_inner + cfg.ssm_inner * d
    attn = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd + cfg.n_heads * hd * d
    layer = ssm + attn + 3 * d * cfg.ffn_dim + 2 * d
    return cfg.n_layers * layer + 2 * cfg.vocab_size * d + d


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape: tuple, std: float, dtype):
    """One matrix, normal with std ``std``, in ``dtype``: one program."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=0)
def _fill_block(buf, key, i, shape: tuple, std: float, axis: int):
    """Block ``i`` along ``axis`` of ``buf`` drawn in place."""
    block = (jax.random.normal(key, shape, jnp.float32) * std).astype(
        buf.dtype)
    at = (i * shape[0], 0) if axis == 0 else (0, i * shape[1])
    return jax.lax.dynamic_update_slice(buf, block, at)


def _normal_rows(key, rows: int, cols: int, std: float, dtype, axis: int):
    """An embedding or a head, drawn a block of the vocabulary at a time
    into the one array (261,120 x 5,120 float32 beside the weights would
    be 5.3 GB, and blocks put together a second copy): ``axis`` the
    vocabulary's."""
    nb = math.gcd(rows, 64)
    shape = (rows // nb, cols) if axis == 0 else (cols, rows // nb)
    buf = jnp.zeros((rows, cols) if axis == 0 else (cols, rows), dtype)
    for i, k in enumerate(jax.random.split(key, nb)):
        buf = _fill_block(buf, k, jnp.int32(i), shape, std, axis)
    return buf


def init_params(key, cfg: FalconH1Config):
    """Seeded weights under which every branch shows (one matrix a
    program, in the served dtype). The multipliers were tuned for TRAINED
    weights: with every matrix at std 1 / sqrt(fan_in) the three branches
    would add about 1 %, 0.7 % and 0.1 % to the residual stream, under
    bf16's own rounding of it, and the logits would span +-0.04. So each
    matrix that meets a multiplier is drawn at std 1 / (multiplier x
    sqrt(fan_in)) (W_in a segment at a time), which leaves every
    pre-activation at unit scale with the multipliers AS PUBLISHED in the
    program; the embedding at 1 / embedding_multiplier. ``a_log`` = log of
    uniform [1, 16], ``dt_bias`` the inverse softplus of exp(uniform[log
    0.001, log 0.1]) (Mamba-2's own initialiser: decays of 0.2 to 0.999 a
    step), ``d`` ones, taps and their bias uniform +-0.5, norms 1."""
    dt = jnp.dtype(cfg.dtype)
    d, h, hkv, hd, f = (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                        cfg.ffn_dim)
    hs, inner = cfg.ssm_heads, cfg.ssm_inner

    def w(k, *shape, fan_in, mult=1.0):
        return _normal(k, shape, 1.0 / (mult * math.sqrt(fan_in)), dt)

    keys = jax.random.split(key, cfg.n_layers + 2)
    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i], 16)
        w_in = jnp.concatenate([
            w(kk, d, width, fan_in=d, mult=cfg.ssm_in_multiplier * m)
            for kk, width, m in zip(jax.random.split(k[7], 5), cfg.segments,
                                    cfg.ssm_multipliers)], axis=1)
        step = jnp.exp(jax.random.uniform(
            k[10], (hs,), jnp.float32, math.log(0.001), math.log(0.1)))
        layers.append({
            "in_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt),
            "attn": {
                "wq": w(k[0], d, h, hd, fan_in=d,
                        mult=cfg.attention_in_multiplier),
                "wk": w(k[1], d, hkv, hd, fan_in=d,
                        mult=cfg.attention_in_multiplier
                        * cfg.key_multiplier),
                "wv": w(k[2], d, hkv, hd, fan_in=d,
                        mult=cfg.attention_in_multiplier),
                "wo": w(k[3], h, hd, d, fan_in=h * hd,
                        mult=cfg.attention_out_multiplier)},
            "ssm": {
                "w_in": w_in,
                "conv_w": jax.random.uniform(
                    k[8], (cfg.ssm_conv, cfg.conv_dim), jnp.float32,
                    -0.5, 0.5).astype(dt),
                "conv_b": jax.random.uniform(
                    k[9], (cfg.conv_dim,), jnp.float32, -0.5, 0.5).astype(dt),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(jax.random.uniform(
                    k[11], (hs,), jnp.float32, 1.0, 16.0)),
                "d": jnp.ones((hs,), jnp.float32),
                "norm": jnp.ones((inner,), dt),
                "w_out": w(k[12], inner, d, fan_in=inner,
                           mult=cfg.ssm_out_multiplier)},
            "mlp": {
                "w_gate": w(k[4], d, f, fan_in=d,
                            mult=cfg.mlp_multipliers[0]),
                "w_up": w(k[5], d, f, fan_in=d),
                "w_down": w(k[6], f, d, fan_in=f,
                            mult=cfg.mlp_multipliers[1])}})
    return {
        "embed": _normal_rows(keys[-2], cfg.vocab_size, d,
                              1.0 / cfg.embedding_multiplier, dt, 0),
        "layers": layers, "final_norm": jnp.ones((d,), dt),
        "lm_head": _normal_rows(
            keys[-1], cfg.vocab_size, d,
            1.0 / (cfg.lm_head_multiplier * math.sqrt(d)), dt, 1)}


def load_params(path: str, cfg: FalconH1Config | None = None):
    raise NotImplementedError(
        "falcon_h1 has no checkpoint reader yet: serve it on seeded weights "
        "(checkpoint_path=None)")


_NO_TP = ("falcon_h1 has no tensor-parallel partition rules yet (20 query "
          "heads, 4 KV heads, 2 state groups and a state pool a slot need "
          "their own): tp_degree must be 1")


def check_tp_divides(cfg: FalconH1Config, tp: int) -> None:
    if tp != 1:
        raise ValueError(_NO_TP)


def serve_partition_rules():
    raise ValueError(_NO_TP)


# ---------------------------------------------------------------------------
# the serving block (models/block.py)
# ---------------------------------------------------------------------------

def cache_spec(cfg: FalconH1Config) -> CacheSpec:
    return CacheSpec(
        paged_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, state_layers=cfg.n_layers,
        state_arrays=(
            (((cfg.ssm_conv - 1) * cfg.conv_dim,), ""),
            ((cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), "float32")),
        state_per_slot=True)


def serve_layers(cfg: FalconH1Config) -> tuple:
    return tuple(LayerDef(mixer="hybrid", ffn="dense", page_layer=i,
                          state_layer=i) for i in range(cfg.n_layers))


def serve_params(params, cfg: FalconH1Config):
    """wq, wk and wv of every layer head-major, [H, D, hd] (``wq_hm`` ...;
    models/block.py ``head_major``)."""
    return {**params, "layers": [
        {**lp, "attn": head_major(lp["attn"], ("wq", "wk", "wv"))}
        for lp in params["layers"]]}


def serve_embed(params, tokens, cfg: FalconH1Config):
    return (params["embed"][tokens] * cfg.embedding_multiplier).astype(
        cfg.dtype)


def _segment_multipliers(cfg: FalconH1Config):
    """``ssm_multipliers`` laid over W_in's columns, float32 [sum of the
    segments]."""
    return jnp.concatenate([jnp.full((width,), m, jnp.float32)
                            for width, m in zip(cfg.segments,
                                                cfg.ssm_multipliers)])


def serve_hybrid_in(x, layer, cos, sin, cfg: FalconH1Config):
    """The layer's one norm, then both mixers' inputs off it: (q, k, v) as
    ``serve_qkv`` gives them (k multiplied and rotated), and (z [B, T, I],
    xbc [B, T, conv_dim] BEFORE the convolution, dt [B, T, heads] before
    its bias and softplus)."""
    a, s = layer["attn"], layer["ssm"]
    with jax.named_scope("norm"):
        u = rms_norm(x, layer["in_norm"], cfg.norm_eps)
    with jax.named_scope("attn"):
        ua = u * jnp.asarray(cfg.attention_in_multiplier, u.dtype)
        q = jnp.einsum("btd,hdk->bthk", ua, a["wq_hm"])
        k = jnp.einsum("btd,hdk->bthk", ua, a["wk_hm"]) \
            * jnp.asarray(cfg.key_multiplier, u.dtype)
        v = jnp.einsum("btd,hdk->bthk", ua, a["wv_hm"])
        qkv = apply_rope(q, cos, sin), apply_rope(k, cos, sin), v
    with jax.named_scope("ssm"), jax.named_scope("ssm_in"):
        p = (u * jnp.asarray(cfg.ssm_in_multiplier, u.dtype)) @ s["w_in"]
        p = (p * _segment_multipliers(cfg)).astype(u.dtype)
        inner = cfg.ssm_inner
        z, xbc, dt = (p[..., :inner], p[..., inner:inner + cfg.conv_dim],
                      p[..., inner + cfg.conv_dim:])
    return qkv, (z, xbc, dt)


def serve_attn_out(attn, layer, cfg: FalconH1Config):
    """The attention branch as the residual takes it: W_o, times
    ``attention_out_multiplier``."""
    out = jnp.einsum("...hk,hkd->...d", attn, layer["attn"]["wo"])
    return out * jnp.asarray(cfg.attention_out_multiplier, out.dtype)


def serve_ssm_conv(ext, layer, cfg: FalconH1Config):
    """The causal convolution over ``ext`` [B, K - 1 + T, conv_dim] (the
    K - 1 columns a sequence kept, then this call's), its bias and silu;
    split into x [B, T, heads, P], B and C [B, T, G, N]; and dt's two
    constants."""
    s = layer["ssm"]
    t = ext.shape[1] - (cfg.ssm_conv - 1)
    conv = sum(s["conv_w"][j] * ext[:, j:j + t] for j in range(cfg.ssm_conv))
    xbc = jax.nn.silu(conv + s["conv_b"])
    inner, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:2]
    return (xbc[..., :inner].reshape(lead + (cfg.ssm_heads, cfg.ssm_head_dim)),
            xbc[..., inner:inner + gn].reshape(
                lead + (cfg.ssm_groups, cfg.ssm_state)),
            xbc[..., inner + gn:].reshape(
                lead + (cfg.ssm_groups, cfg.ssm_state)))


def serve_ssm_step(dt, layer):
    """(dt after its bias and softplus, float32 [B, T, heads]; A float32
    [heads], negative)."""
    s = layer["ssm"]
    return (jax.nn.softplus(dt.astype(jnp.float32) + s["dt_bias"]),
            -jnp.exp(s["a_log"].astype(jnp.float32)))


def serve_ssm_out(y, xs, z, layer, cfg: FalconH1Config):
    """The state-space branch as the residual takes it, from the scan's y
    [B, T, heads, P] float32: the skip ``d * x``, the gate, the grouped RMS
    norm (gate FIRST), ``W_out``, times ``ssm_out_multiplier``."""
    s = layer["ssm"]
    y = y + s["d"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
    y = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(y.shape[:-1] + (cfg.ssm_groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.norm_eps)
    y = (grouped.reshape(y.shape) * s["norm"]).astype(z.dtype)
    return (y @ s["w_out"]) * jnp.asarray(cfg.ssm_out_multiplier, z.dtype)


def serve_ffn(x, layer, cfg: FalconH1Config, ld: LayerDef):
    """x + the SwiGLU under its two multipliers; no expert choice."""
    m = layer["mlp"]
    with jax.named_scope("norm"):
        g = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        gate = jax.nn.silu((g @ m["w_gate"])
                           * jnp.asarray(cfg.mlp_multipliers[0], g.dtype))
        out = ((g @ m["w_up"]) * gate) @ m["w_down"]
        return x + out * jnp.asarray(cfg.mlp_multipliers[1], g.dtype), None


def serve_final_norm(x, params, cfg: FalconH1Config):
    with jax.named_scope("norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def serve_lm_head(x, params, cfg: FalconH1Config):
    """The untied head, float32 logits, times ``lm_head_multiplier``."""
    with jax.named_scope("lm_head"):
        return (x @ params["lm_head"]).astype(jnp.float32) \
            * cfg.lm_head_multiplier
