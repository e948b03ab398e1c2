"""Plain float32 reference of Falcon-H1 (``model_type`` ``falcon_h1``),
written from the layer equations in ISSUE 60 (the published modelling code
as its writer knows it) and importing nothing of ray_tpu or of the
adapter: float32 everywhere, every matmul at precision "highest", no
cache, no chunk, no kernel, one sequence at a time, the state-space
recurrence a ``lax.scan`` over SINGLE tokens, one layer cast to float32 at
a time and the head a block of the vocabulary at a time. The parameter
pytree is DATA, in the layout the program keeps it: ``layers`` a list, a
layer ``in_norm`` / ``ffn_norm`` [D], ``attn`` (wq [D, H, hd], wk / wv [D,
Hkv, hd], wo [H, hd, D]), ``ssm`` (w_in [D, I + (I + 2 G N) + Hs], conv_w
[K, I + 2 G N], conv_b, dt_bias / a_log / d [Hs], norm [I], w_out [I, D])
and ``mlp`` (w_gate / w_up [D, F], w_down [F, D]); ``embed`` [V, D],
``final_norm`` [D], ``lm_head`` [D, V].

    x   = E[token] * embedding_multiplier
    u   = rms(x, in_norm)
    x   = x + ssm_out_multiplier * Mamba(u)
            + attention_out_multiplier * Attn(u * attention_in_multiplier)
    v   = rms(x, ffn_norm)
    x   = x + mlp_multipliers[1] * W_down(W_up v * silu(mlp_multipliers[0]
                                                        * W_gate v))
    logits = lm_head_multiplier * W_head rms(x, final_norm)

    Attn:  q = W_q a, k = key_multiplier * W_k a, v = W_v a; rotary over all
           lanes, pairs (2i, 2i + 1); causal softmax at hd ** -0.5, query
           head j on KV head j // (H / Hkv); W_o
    Mamba: p = W_in (ssm_in_multiplier * u), times ssm_multipliers over the
           segments z | x | B | C | dt; xBC_t = silu(sum_k conv_w[k] *
           xBC_{t-K+1+k} + conv_b), zeros before the sequence;
           dt_t = softplus(dt_t + dt_bias), A = -exp(a_log);
           S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer)
           B_t[g(h)], S [Hs, P, N] zeros before the sequence, head h on
           group h // (Hs / G); y_t[h] = S_t[h] C_t[g(h)] + d[h] x_t[h];
           y_t = rms over each of the G groups of I / G lanes of (y_t *
           silu(z_t)), times norm (gate FIRST); W_out

Keywords (the adapter's ``reference_kwargs``): theta, eps, groups, state
(N), head_p (P), the seven multipliers. The negative controls' overrides,
each leaving one rule out or wrong: ``mamba=False`` / ``attention=False`` /
``mlp=False`` (a branch left out), any multiplier at 1, ``skip=False`` (no
``d * x``), ``dt_bias=False``, ``norm_before_gate=True``, ``one_group=True``
(every head reads group 0 and the norm runs over all I lanes),
``conv_bias=False``, ``state_reset_every=n`` (state and taps forgotten
every n positions: what a program does that does not carry them from one
prefill chunk to the next).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_BLOCK = 32768


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


def _attention(u, a, theta, key_multiplier):
    """One sequence's normed input u [T, D] through the attention mixer,
    before its output multiplier."""
    q = jnp.einsum("td,dhk->thk", u, _f32(a["wq"]), precision=HIGHEST)
    k = jnp.einsum("td,dhk->thk", u, _f32(a["wk"]), precision=HIGHEST) \
        * key_multiplier
    v = jnp.einsum("td,dhk->thk", u, _f32(a["wv"]), precision=HIGHEST)
    q, k = _rope(q, theta), _rope(k, theta)
    n_rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, n_rep, axis=1), jnp.repeat(v, n_rep, axis=1)
    t = u.shape[0]
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    return jnp.einsum("thk,hkd->td", o, _f32(a["wo"]), precision=HIGHEST)


def _mamba(u, m, eps, groups, state, head_p, ssm_in_multiplier,
           ssm_multipliers, skip, dt_bias, norm_before_gate, one_group,
           conv_bias, state_reset_every):
    """One sequence's normed input u [T, D] through the state-space mixer,
    before its output multiplier; the recurrence token by token."""
    t = u.shape[0]
    heads = m["a_log"].shape[0]
    inner, gn = heads * head_p, groups * state
    p = jnp.dot(u * ssm_in_multiplier, _f32(m["w_in"]), precision=HIGHEST)
    p = p * jnp.concatenate([
        jnp.full((width,), mult, jnp.float32) for width, mult in zip(
            (inner, inner, gn, gn, heads), ssm_multipliers)])
    z, xbc, dt = (p[:, :inner], p[:, inner:2 * inner + 2 * gn],
                  p[:, 2 * inner + 2 * gn:])
    kernel = _f32(m["conv_w"])                                  # [K, C]
    taps = kernel.shape[0]
    pos = jnp.arange(t)
    conv = jnp.zeros_like(xbc)
    for j in range(taps):
        back = taps - 1 - j                   # tap j reads column t - back
        col = jnp.pad(xbc, ((back, 0), (0, 0)))[:t]
        if state_reset_every:     # a column before the last reset is gone
            col = jnp.where(((pos % state_reset_every) >= back)[:, None],
                            col, 0.0)
        conv = conv + kernel[j] * col
    if conv_bias:
        conv = conv + _f32(m["conv_b"])
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(t, heads, head_p)
    b = xbc[:, inner:inner + gn].reshape(t, groups, state)
    c = xbc[:, inner + gn:].reshape(t, groups, state)
    of = jnp.zeros((heads,), jnp.int32) if one_group \
        else jnp.arange(heads) // (heads // groups)
    b, c = b[:, of], c[:, of]                                   # [T, Hs, N]
    dt = jax.nn.softplus(dt + (_f32(m["dt_bias"]) if dt_bias else 0.0))
    rate = -jnp.exp(_f32(m["a_log"]))

    def token(s, inp):
        x_t, b_t, c_t, dt_t, fresh = inp
        s = jnp.where(fresh, 0.0, s)
        s = jnp.exp(dt_t * rate)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=HIGHEST)

    fresh = (pos % state_reset_every == 0) if state_reset_every \
        else jnp.zeros((t,), bool)
    _, y = jax.lax.scan(token, jnp.zeros((heads, head_p, state), jnp.float32),
                        (x, b, c, dt, fresh))
    if skip:
        y = y + _f32(m["d"])[None, :, None] * x
    y = y.reshape(t, inner)
    gate = jax.nn.silu(z)
    norm_groups = 1 if one_group else groups

    def group_norm(v):
        g = v.reshape(t, norm_groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return g.reshape(t, inner) * _f32(m["norm"])

    y = group_norm(y) * gate if norm_before_gate else group_norm(y * gate)
    return jnp.dot(y, _f32(m["w_out"]), precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=(
    "theta", "eps", "groups", "state", "head_p", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers", "mamba",
    "attention", "mlp", "skip", "dt_bias", "norm_before_gate", "one_group",
    "conv_bias", "state_reset_every"))
def _layer(x, lp, *, theta, eps, groups, state, head_p,
           attention_in_multiplier, attention_out_multiplier, key_multiplier,
           ssm_in_multiplier, ssm_out_multiplier, ssm_multipliers,
           mlp_multipliers, mamba=True, attention=True, mlp=True, skip=True,
           dt_bias=True, norm_before_gate=False, one_group=False,
           conv_bias=True, state_reset_every=0):
    """One sequence x [T, D] through one layer."""
    u = _rms_norm(x, lp["in_norm"], eps)
    add = jnp.zeros_like(x)
    if mamba:
        add = add + ssm_out_multiplier * _mamba(
            u, lp["ssm"], eps, groups, state, head_p, ssm_in_multiplier,
            ssm_multipliers, skip, dt_bias, norm_before_gate, one_group,
            conv_bias, state_reset_every)
    if attention:
        add = add + attention_out_multiplier * _attention(
            u * attention_in_multiplier, lp["attn"], theta, key_multiplier)
    x = x + add
    if mlp:
        v = _rms_norm(x, lp["ffn_norm"], eps)
        m = lp["mlp"]
        gate = jax.nn.silu(mlp_multipliers[0] * jnp.dot(
            v, _f32(m["w_gate"]), precision=HIGHEST))
        up = jnp.dot(v, _f32(m["w_up"]), precision=HIGHEST)
        x = x + mlp_multipliers[1] * jnp.dot(
            up * gate, _f32(m["w_down"]), precision=HIGHEST)
    return x


@jax.jit
def _embed(embed, tokens, mult):
    return embed[tokens].astype(jnp.float32) * mult


def _split(kw):
    """(the layers' keywords, the embedding's multiplier, the head's)."""
    kw = dict(kw)
    return kw, kw.pop("embedding_multiplier"), kw.pop("lm_head_multiplier")


def hidden(params, tokens, **kw):
    """tokens [B, T] -> hidden states before the final norm [B, T, D]."""
    kw, embed_mult, _ = _split(kw)
    kw["ssm_multipliers"] = tuple(kw["ssm_multipliers"])
    kw["mlp_multipliers"] = tuple(kw["mlp_multipliers"])
    out = []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = _embed(params["embed"], jnp.asarray(seq, jnp.int32),
                       embed_mult)
            for lp in params["layers"]:
                x = _layer(x, lp, **kw)
            out.append(x)
    return jnp.stack(out)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, final_norm, block, eps, mult):
    return jnp.dot(_rms_norm(x, final_norm, eps), _f32(block),
                   precision=HIGHEST) * mult


def _head(x, params, eps, mult):
    """Logits of x [..., D], the head cast to float32 a block of the
    vocabulary at a time (whole: 5.3 GB beside the weights)."""
    head = params["lm_head"]
    v = head.shape[1]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate([
            _head_block(x, params["final_norm"],
                        head[:, at:min(at + VOCAB_BLOCK, v)], eps, mult)
            for at in range(0, v, VOCAB_BLOCK)], axis=-1)


def logits_at(params, tokens, positions, **kw):
    """Logits [B, len(positions), V]."""
    x = hidden(params, tokens, **kw)
    return _head(x[:, jnp.asarray(positions)], params, kw["eps"],
                 kw["lm_head_multiplier"])


def deficits(params, hidden_i, first, served, n, **kw):
    w = served.shape[0]
    x = jax.lax.dynamic_slice_in_dim(hidden_i, first, w, axis=0)
    lg = _head(x, params, kw["eps"], kw["lm_head_multiplier"])
    return _deficit(lg, served, n)


@jax.jit
def _deficit(lg, served, n):
    gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
        lg, served[:, None], axis=-1)[:, 0]
    live = jnp.arange(lg.shape[0]) < n
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
