"""Plain float32 reference of LFM2-MoE (``model_type`` ``lfm2_moe``), written
from the layer equations in ISSUE 35 / the published modelling code, and
importing nothing of ray_tpu or of the adapter: float32 everywhere, every
matmul at precision "highest", no cache, no state, one sequence at a time,
one layer (and one expert) cast to float32 at a time. The parameter pytree
is DATA, in the layout the program keeps it: ``layers`` is a list; a layer
with ``conv`` mixes by the gated short convolution (w_in [D, 3D], kernel
[K, D], w_out [D, D]), one with ``attn`` by grouped-query attention (wq
[D, H, hd], wk / wv [D, Hkv, hd], wo [H, hd, D], q_norm / k_norm [hd]); a
layer with ``mlp`` has a dense SwiGLU and one with ``moe`` routes (router
[D, E], bias [E], w_gate / w_up [E, D, F], w_down [E, F, D]); norms
``op_norm`` and ``ffn_norm``; the output head is the embedding.

    h = x + mixer(rms(x, op_norm));  y = h + ffn(rms(h, ffn_norm))
    conv:  [B, C, u] = split3(z W_in); v = B * u;
           c_t = sum_j kernel[j] * v_{t-K+1+j} (zeros before the start);
           out = (C * c) W_out
    attn:  q = rope(rms(z W_q, q_norm)), k = rope(rms(z W_k, k_norm)) per
           head, the rotation pairing lanes (2i, 2i + 1); causal softmax
           attention scaled by hd ** -0.5; W_o
    moe:   s = sigmoid(g W_r); chosen = top_k of s + bias; weights
           s_e / (sum of the chosen s + 1e-6) * scaling

Routing (the contract of benchmark/reference/__init__.py for a routed
family): without ``routing`` a token takes this file's own choice; with
``routing`` [L_r, T, k] it takes the experts named there. Either way the
weights are this file's own float32 ones, and ``routing_slack`` holds the
choice to the selection scores WITH the bias.

Keywords (the adapter's ``reference_kwargs``): theta, eps, top_k, scaling,
norm_topk, use_bias; and the negative controls' overrides, each leaving one
rule out: ``qk_norm=False``, ``norm_topk=False``, ``bias_weighs=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _rope(x, theta):
    """x [T, H, hd]: lanes (2i, 2i + 1) rotated against each other by
    pos * theta^(-2i/hd)."""
    t, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "qk_norm"))
def _attention(x, lp, theta, eps, qk_norm):
    """One sequence x [T, D] through a layer's attention mixer."""
    a = lp["attn"]
    h = _rms_norm(x, lp["op_norm"], eps)
    q = jnp.einsum("td,dhk->thk", h, _f32(a["wq"]), precision=HIGHEST)
    k = jnp.einsum("td,dhk->thk", h, _f32(a["wk"]), precision=HIGHEST)
    v = jnp.einsum("td,dhk->thk", h, _f32(a["wv"]), precision=HIGHEST)
    if qk_norm:
        q, k = _rms_norm(q, a["q_norm"], eps), _rms_norm(k, a["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    n_rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, n_rep, axis=1), jnp.repeat(v, n_rep, axis=1)
    t = x.shape[0]
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    return x + jnp.einsum("thk,hkd->td", o, _f32(a["wo"]), precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("eps",))
def _conv(x, lp, eps):
    """One sequence x [T, D] through a layer's gated short convolution."""
    c = lp["conv"]
    z = _rms_norm(x, lp["op_norm"], eps)
    gate_b, gate_c, u = jnp.split(
        jnp.dot(z, _f32(c["w_in"]), precision=HIGHEST), 3, axis=-1)
    kernel = _f32(c["kernel"])                                   # [K, D]
    taps, t = kernel.shape[0], x.shape[0]
    v = jnp.pad(gate_b * u, ((taps - 1, 0), (0, 0)))
    conv = sum(kernel[j] * v[j:j + t] for j in range(taps))
    return x + jnp.dot(gate_c * conv, _f32(c["w_out"]), precision=HIGHEST)


def _swiglu(g, w_gate, w_up, w_down):
    gate = jax.nn.silu(jnp.dot(g, _f32(w_gate), precision=HIGHEST))
    return jnp.dot(gate * jnp.dot(g, _f32(w_up), precision=HIGHEST),
                   _f32(w_down), precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, lp, eps):
    m = lp["mlp"]
    return x + _swiglu(_rms_norm(x, lp["ffn_norm"], eps), m["w_gate"],
                       m["w_up"], m["w_down"])


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "scaling", "norm_topk", "use_bias", "bias_weighs"))
def _routed(x, lp, taken, eps, top_k, scaling, norm_topk, use_bias,
            bias_weighs):
    """x [T, D]; ``taken`` [T, k] the experts to take, or None for this
    file's own choice. Returns (x, slack [T]): per token the k-th largest
    selection score minus the smallest among the taken (0 for the own
    choice; inf for an expert named twice or not there)."""
    moe = lp["moe"]
    g = _rms_norm(x, lp["ffn_norm"], eps)
    s = jax.nn.sigmoid(jnp.dot(g, _f32(moe["router"]), precision=HIGHEST))
    sel = s + _f32(moe["bias"]) if use_bias else s
    n_experts = sel.shape[-1]
    kth = jnp.sort(sel, axis=-1)[:, n_experts - top_k]
    if taken is None:
        taken = jax.lax.top_k(sel, top_k)[1]
    there = (taken >= 0) & (taken < n_experts)
    ids = jnp.clip(taken, 0, n_experts - 1)
    hot = jax.nn.one_hot(ids, n_experts, dtype=jnp.float32)      # [T, k, E]
    sound = jnp.all(there, axis=-1) & jnp.all(jnp.sum(hot, axis=1) <= 1.0,
                                              axis=-1)
    slack = jnp.where(
        sound, kth - jnp.min(jnp.take_along_axis(sel, ids, axis=-1), axis=-1),
        jnp.inf)
    w = jnp.take_along_axis(sel if bias_weighs else s, ids, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * scaling
    combine = jnp.sum(hot * w[..., None], axis=1)                # [T, E]

    def one(acc, e):
        y = _swiglu(g, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e])
        return acc + y * combine[:, e][:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_experts))
    return x + y, slack


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, embed, eps):
    return jnp.dot(_rms_norm(x, final_norm, eps), _f32(embed).T,
                   precision=HIGHEST)


def _run(params, tokens, routing, *, theta, eps, top_k, scaling,
         norm_topk=True, use_bias=True, qk_norm=True, bias_weighs=False):
    """One sequence tokens [T] -> (hidden [T, D], slack [L_r, T])."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        slack = []
        for lp in params["layers"]:
            if "conv" in lp:
                x = _conv(x, lp, eps)
            else:
                x = _attention(x, lp, theta, eps, qk_norm)
            if "mlp" in lp:
                x = _dense(x, lp, eps)
            else:
                taken = None if routing is None else jnp.asarray(
                    routing[len(slack)], jnp.int32)
                x, sl = _routed(x, lp, taken, eps, top_k, scaling, norm_topk,
                                use_bias, bias_weighs)
                slack.append(sl)
        return x, (jnp.stack(slack) if slack
                   else jnp.zeros((0, x.shape[0]), jnp.float32))


def _forced(tokens, routing):
    if routing is not None and len(tokens) != 1:
        raise ValueError("routing= names one sequence's experts: tokens [1, T]")
    return routing


def hidden(params, tokens, *, routing=None, **kw):
    """tokens [B, T] -> hidden states before the final norm [B, T, D]."""
    _forced(tokens, routing)
    return jnp.stack([_run(params, t, routing, **kw)[0] for t in tokens])


def logits_at(params, tokens, positions, *, routing=None, **kw):
    """Logits [B, len(positions), V]; with ``routing`` [L_r, T, k] (B = 1)
    through the experts named there."""
    x = hidden(params, tokens, routing=routing, **kw)
    with jax.default_matmul_precision("highest"):
        return _head(x[:, jnp.asarray(positions)], params["final_norm"],
                     params["embed"], kw["eps"])


def routing_slack(params, tokens, routing, **kw):
    """float32 [L_r, T]: per decision of ``routing`` [L_r, T, k] (tokens
    [1, T]) this file's k-th largest selection score minus the smallest
    among the experts named, on the hidden states that taking the named
    experts gives."""
    _forced(tokens, routing)
    return _run(params, tokens[0], routing, **kw)[1]


def deficits(params, hidden_i, first, served, n, **kw):
    return _deficits(hidden_i, first, served, n, params["final_norm"],
                     params["embed"], kw["eps"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _deficits(hidden_i, first, served, n, final_norm, embed, eps):
    w = served.shape[0]
    with jax.default_matmul_precision("highest"):
        lg = _head(jax.lax.dynamic_slice_in_dim(hidden_i, first, w, axis=0),
                   final_norm, embed, eps)
    gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
        lg, served[:, None], axis=-1)[:, 0]
    live = jnp.arange(w) < n
    return (jnp.max(jnp.where(live, gap, 0.0)),
            jnp.all(jnp.where(live[:, None], jnp.isfinite(lg), True)))


def loss(params, tokens, **kw):
    """Mean next-token cross-entropy of tokens [B, T + 1]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lg = logits_at(params, tokens[:, :-1], jnp.arange(tokens.shape[1] - 1),
                   **kw)
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return float(jnp.mean(nll))
