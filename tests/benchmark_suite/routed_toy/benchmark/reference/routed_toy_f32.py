"""Plain float32 reference of the toy routed family (its block is
described in the adapter beside benchmark/models/; this file follows that
description and imports nothing of the adapter or of ray_tpu): float32
everywhere, every matmul at precision "highest", no cache, one sequence
at a time, one expert cast to float32 at a time. The parameter pytree is
DATA: ``layers`` is a list, a layer with ``mlp`` is dense and one with
``moe`` routes (router [D, E], bias [E], w_gate / w_up [E, D, F], w_down
[E, F, D]); wq [D, H, hd], wk / wv [D, Hkv, hd], wo [H, hd, D]; the output
head is the embedding.

Routing (the contract of benchmark/reference/__init__.py for a routed
family): without ``routing`` a token takes the ``top_k`` experts of
``sigmoid(g W_r) + bias``; with ``routing`` [L_r, T, k] it takes the
experts named there. Either way the weights are this file's own float32
``s_i / (sum over the taken s + 1e-6) * scaling``.
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
    """x [T, H, hd]: the halves (x[:hd/2], x[hd/2:]) rotated against each
    other by pos * theta^(-2i/hd)."""
    t, _, hd = x.shape
    inv = theta ** (-jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _attention(x, lp, theta, eps):
    """One sequence x [T, D] through a layer's attention."""
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = _rope(jnp.einsum("td,dhk->thk", h, _f32(lp["wq"]), precision=HIGHEST),
              theta)
    k = _rope(jnp.einsum("td,dhk->thk", h, _f32(lp["wk"]), precision=HIGHEST),
              theta)
    v = jnp.einsum("td,dhk->thk", h, _f32(lp["wv"]), precision=HIGHEST)
    n_rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, n_rep, axis=1), jnp.repeat(v, n_rep, axis=1)
    t = x.shape[0]
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    return x + jnp.einsum("thk,hkd->td", a, _f32(lp["wo"]), precision=HIGHEST)


def _swiglu(g, w_gate, w_up, w_down):
    gate = jax.nn.silu(jnp.dot(g, _f32(w_gate), precision=HIGHEST))
    return jnp.dot(gate * jnp.dot(g, _f32(w_up), precision=HIGHEST),
                   _f32(w_down), precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, lp, eps):
    m = lp["mlp"]
    return x + _swiglu(_rms_norm(x, lp["ffn_norm"], eps), m["w_gate"],
                       m["w_up"], m["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "scaling"))
def _routed(x, lp, taken, eps, top_k, scaling):
    """x [T, D]; ``taken`` [T, k] the experts to take, or None for this
    file's own choice. Returns (x, slack [T]): per token the k-th largest
    selection score minus the smallest among the taken (0 for the own
    choice; inf for an expert named twice or not there)."""
    moe = lp["moe"]
    g = _rms_norm(x, lp["ffn_norm"], eps)
    s = jax.nn.sigmoid(jnp.dot(g, _f32(moe["router"]), precision=HIGHEST))
    sel = s + _f32(moe["bias"])
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
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * scaling
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


def _run(params, tokens, routing, *, theta, eps, top_k, scaling):
    """One sequence tokens [T] -> (hidden [T, D], slack [L_r, T])."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        slack = []
        for lp in params["layers"]:
            x = _attention(x, lp, theta, eps)
            if "mlp" in lp:
                x = _dense(x, lp, eps)
            else:
                taken = None if routing is None else jnp.asarray(
                    routing[len(slack)], jnp.int32)
                x, sl = _routed(x, lp, taken, eps, top_k, scaling)
                slack.append(sl)
        return x, jnp.stack(slack)


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
