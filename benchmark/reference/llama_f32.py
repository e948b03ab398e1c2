"""Plain float32 reference of the dense Llama/Mistral block.

Follows the published description (Mistral-7B: pre-norm residual block,
RMSNorm, grouped-query attention with rotary embeddings on interleaved
pairs as in mistral-inference, SwiGLU, untied output head, no sliding
window in v0.3) in straightforward jax.numpy: float32 everywhere, every
matmul at precision "highest", no kernel, no cache, no batching tricks.
Independent of ray_tpu: it takes the parameter pytree as DATA, in the
layout the program keeps it (layers stacked on axis 0; wq [D, H, hd],
wk/wv [D, Hkv, hd], wo [H, hd, D], w_gate/w_up [D, F], w_down [F, D]).

One layer's weights are cast to float32 at a time (``run_layers`` slices
the stack), so a 16-layer model at published widths fits beside its own
bf16 weights on one 16 GB chip.

Departures from the published model: none in the mathematics. Weights are
random (from a seed), and the depth is whatever the pytree holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, H, hd]: rotate the pairs (x[2i], x[2i+1]) by pos * theta^(-2i/hd)."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _block(x, lp, *, theta, eps, use_rope=True):
    """One sequence x [T, D] through one block."""
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    wq, wk, wv, wo = (f32(lp["attn"][k]) for k in ("wq", "wk", "wv", "wo"))
    h = _rms_norm(x, f32(lp["attn_norm"]), eps)
    q = jnp.einsum("td,dhk->thk", h, wq, precision=HIGHEST)
    k = jnp.einsum("td,dhk->thk", h, wk, precision=HIGHEST)
    v = jnp.einsum("td,dhk->thk", h, wv, precision=HIGHEST)
    if use_rope:
        q, k = _rope(q, theta), _rope(k, theta)
    n_rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, n_rep, axis=1)      # query head h reads kv head h // n_rep
    v = jnp.repeat(v, n_rep, axis=1)
    t = x.shape[0]
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    x = x + jnp.einsum("thk,hkd->td", a, wo, precision=HIGHEST)
    h = _rms_norm(x, f32(lp["mlp_norm"]), eps)
    gate = jax.nn.silu(jnp.dot(h, f32(lp["mlp"]["w_gate"]), precision=HIGHEST))
    up = jnp.dot(h, f32(lp["mlp"]["w_up"]), precision=HIGHEST)
    return x + jnp.dot(gate * up, f32(lp["mlp"]["w_down"]), precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "use_rope"))
def _layer_batch(x, lp, theta, eps, use_rope=True):
    """x [B, T, D]: sequences one after another (lax.map), so the score
    matrix in memory is one sequence's."""
    return jax.lax.map(
        lambda xi: _block(xi, lp, theta=theta, eps=eps, use_rope=use_rope), x)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, eps):
    """x [..., D] -> logits [..., V]."""
    h = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    return jnp.dot(h, lm_head.astype(jnp.float32), precision=HIGHEST)


def hidden(params, tokens, *, theta: float, eps: float, use_rope: bool = True):
    """tokens [B, T] -> hidden states before the final norm [B, T, D],
    float32, one layer cast to float32 at a time."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
        for i in range(n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x = _layer_batch(x, lp, theta, eps, use_rope)
        return x


def logits_at(params, tokens, positions, *, theta: float, eps: float,
              use_rope: bool = True):
    """Reference logits [B, len(positions), V] at the given positions of
    each sequence (logits at position p predict token p + 1)."""
    x = hidden(params, tokens, theta=theta, eps=eps, use_rope=use_rope)
    with jax.default_matmul_precision("highest"):
        return _head(x[:, jnp.asarray(positions)], params["final_norm"],
                     params["lm_head"], eps)


def deficits(params, hidden_i, first, served, n, *, theta: float, eps: float,
             use_rope: bool = True):
    """For one sequence's hidden states [T, D]: the largest (reference max
    logit - reference logit of the served token) over the n tokens served
    from position ``first`` + 1 on, and whether every logit is finite.
    ``served`` is padded to a fixed width; ``first`` and ``n`` are traced,
    so one program serves every sample. (``theta`` and ``use_rope`` are
    the contract's keywords; the head needs neither.)"""
    return _deficits(hidden_i, first, served, n, params["final_norm"],
                     params["lm_head"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _deficits(hidden_i, first, served, n, final_norm, lm_head, eps):
    w = served.shape[0]
    with jax.default_matmul_precision("highest"):
        lg = _head(jax.lax.dynamic_slice_in_dim(hidden_i, first, w, axis=0),
                   final_norm, lm_head, eps)
    gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
        lg, served[:, None], axis=-1)[:, 0]
    live = jnp.arange(w) < n
    return (jnp.max(jnp.where(live, gap, 0.0)),
            jnp.all(jnp.where(live[:, None], jnp.isfinite(lg), True)))


def loss(params, tokens, *, theta: float, eps: float, use_rope: bool = True):
    """Mean next-token cross-entropy of tokens [B, T + 1], sequence by
    sequence so that one sequence's float32 logits are live at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = hidden(params, tokens[:, :-1], theta=theta, eps=eps,
               use_rope=use_rope)

    with jax.default_matmul_precision("highest"):
        total = sum(float(_seq_nll(x[i], tokens[i, 1:], params["final_norm"],
                                   params["lm_head"], eps))
                    for i in range(x.shape[0]))
    return total / (x.shape[0] * x.shape[1])


@functools.partial(jax.jit, static_argnames=("eps",))
def _seq_nll(xi, yi, final_norm, lm_head, eps):
    lg = _head(xi, final_norm, lm_head, eps)
    return jnp.sum(jax.nn.logsumexp(lg, axis=-1)
                   - jnp.take_along_axis(lg, yi[:, None], axis=-1)[:, 0])
