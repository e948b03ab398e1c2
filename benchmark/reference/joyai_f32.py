"""Plain float32 reference of JoyAI-LLM-Flash (``model_type``
``joyai_llm_flash``: the DeepSeek-V3 block), written from the layer
equations in ISSUE 44 / the published modelling code, and importing nothing
of ray_tpu or of the adapter: float32 everywhere, every matmul at precision
"highest", no cache, no kernel, one sequence at a time, one head's scores
and one expert cast to float32 at a time. The parameter pytree is DATA, in
the layout the program keeps it: ``layers`` is a list; every layer has
``attn`` (wq_a [D, Rq], q_norm [Rq], wq_b [Rq, H, nope + rope], wkv_a [D,
Rkv + rope], kv_norm [Rkv], w_uk [Rkv, H, nope], w_uv [Rkv, H, v]: the
published ``kv_b_proj`` as its two halves; wo [H, v, D]); a layer with
``mlp`` has a dense SwiGLU and one with ``moe`` routes (router [D, E], bias
[E], w_gate / w_up [E, D, F], w_down [E, F, D], ``shared``: one more SwiGLU
on every token); norms ``attn_norm`` and ``ffn_norm``; an untied ``lm_head``
[D, V]. The widths are read off the arrays.

ONLY THE EXPANDED FORM of the mixer is here (the published one), so every
comparison of the program's decode through its latent cache with this file
checks the absorption:

    h = rms(x, attn_norm)
    c_q = rms(h W_qa, q_norm);  q = c_q W_qb = [q_nope | q_rope] per head
    [c_kv | k_r] = h W_kva;  c_kv <- rms(c_kv, kv_norm)
    q_rope, k_r rotated, lanes (2i, 2i + 1) paired (rope_interleave), k_r
        ONE vector a token shared by all heads
    k_h = [c_kv W_uk[h] | k_r];  v_h = c_kv W_uv[h]
    a_h = softmax(q_h k_h^T / sqrt(nope + rope) + causal) v_h;  out = a W_o
    moe: s = sigmoid(g W_r); groups: with n_group = topk_group = 1 the
         published group-limited step keeps every expert (written below as
         the identity it is); chosen = top_k of s + bias; weights
         s_e / (sum of the chosen s + 1e-20) * scaling;
         y = sum_e w_e SwiGLU_e(g) + SwiGLU_shared(g)
``rope_scaling`` is null (no YaRN term in the scale); the multi-token
prediction block is not part of generation and not here.

Routing (the contract of benchmark/reference/__init__.py for a routed
family): without ``routing`` a token takes this file's own choice; with
``routing`` [L_r, T, k] it takes the experts named there. Either way the
weights are this file's own float32 ones, and ``routing_slack`` holds the
choice to the selection scores WITH the bias.

Keywords (the adapter's ``reference_kwargs``): theta, eps, top_k, scaling,
norm_topk; and the negative controls' overrides, each leaving one rule out
or getting it wrong: ``scale_dim`` (the softmax scale's width: nope + rope
when None), ``rotate_k=False``, ``kv_norm=False``, ``q_norm=False``,
``value_from`` (the lane of [c_kv | k_r] the values start at: 0),
``shared=False``, ``use_bias=False``, ``bias_weighs=True``; the factor is
left out by ``scaling=1.0``.
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


@functools.partial(jax.jit, static_argnames=(
    "theta", "eps", "scale_dim", "rotate_k", "kv_norm", "q_norm",
    "value_from"))
def _attention(x, lp, theta, eps, scale_dim, rotate_k, kv_norm, q_norm,
               value_from):
    """One sequence x [T, D] through a layer's latent-attention mixer, in
    the expanded form."""
    a = lp["attn"]
    rank, _, nope = a["w_uk"].shape
    h = _rms_norm(x, lp["attn_norm"], eps)
    c_q = jnp.dot(h, _f32(a["wq_a"]), precision=HIGHEST)
    if q_norm:
        c_q = _rms_norm(c_q, a["q_norm"], eps)
    q = jnp.einsum("tr,rhk->thk", c_q, _f32(a["wq_b"]), precision=HIGHEST)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
    ckr = jnp.dot(h, _f32(a["wkv_a"]), precision=HIGHEST)
    c_kv, k_r = ckr[:, :rank], ckr[:, rank:]
    if kv_norm:
        c_kv = _rms_norm(c_kv, a["kv_norm"], eps)
    if rotate_k:
        k_r = _rope(k_r[:, None, :], theta)[:, 0]
    k_nope = jnp.einsum("tr,rhn->thn", c_kv, _f32(a["w_uk"]),
                        precision=HIGHEST)
    # what the values are made from: c_kv (``value_from`` 0), or the same
    # number of lanes of [c_kv | k_r] from another lane on (a control)
    src = jnp.concatenate([c_kv, k_r], axis=-1)[:, value_from:
                                                value_from + rank]
    v = jnp.einsum("tr,rhv->thv", src, _f32(a["w_uv"]), precision=HIGHEST)
    t = x.shape[0]
    scale = (scale_dim or q.shape[-1]) ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(args):                      # one head's [T, T] scores a time
        qn, qr, kn, vh = args
        s = (jnp.dot(qn, kn.T, precision=HIGHEST)
             + jnp.dot(qr, k_r.T, precision=HIGHEST)) * scale
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.dot(jax.nn.softmax(s, axis=-1), vh, precision=HIGHEST)

    o = jax.lax.map(head, (q_nope.swapaxes(0, 1), q_rope.swapaxes(0, 1),
                           k_nope.swapaxes(0, 1), v.swapaxes(0, 1)))
    return x + jnp.einsum("htv,hvd->td", o, _f32(a["wo"]), precision=HIGHEST)


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
    "eps", "top_k", "scaling", "norm_topk", "use_bias", "bias_weighs",
    "shared"))
def _routed(x, lp, taken, eps, top_k, scaling, norm_topk, use_bias,
            bias_weighs, shared):
    """x [T, D]; ``taken`` [T, k] the experts to take, or None for this
    file's own choice. Returns (x, slack [T]): per token the k-th largest
    selection score minus the smallest among the taken (0 for the own
    choice; inf for an expert named twice or not there)."""
    moe = lp["moe"]
    g = _rms_norm(x, lp["ffn_norm"], eps)
    s = jax.nn.sigmoid(jnp.dot(g, _f32(moe["router"]), precision=HIGHEST))
    sel = s + _f32(moe["bias"]) if use_bias else s
    # the published group-limited step (noaux_tc): experts in n_group
    # groups, the topk_group best groups kept. n_group = topk_group = 1:
    # one group, kept, so every expert stays eligible: the identity.
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
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * scaling
    combine = jnp.sum(hot * w[..., None], axis=1)                # [T, E]

    def one(acc, e):
        y = _swiglu(g, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e])
        return acc + y * combine[:, e][:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_experts))
    if shared:
        sh = moe["shared"]
        y = y + _swiglu(g, sh["w_gate"], sh["w_up"], sh["w_down"])
    return x + y, slack


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, eps):
    return jnp.dot(_rms_norm(x, final_norm, eps), _f32(lm_head),
                   precision=HIGHEST)


def _run(params, tokens, routing, *, theta, eps, top_k, scaling,
         norm_topk=True, use_bias=True, bias_weighs=False, shared=True,
         scale_dim=None, rotate_k=True, kv_norm=True, q_norm=True,
         value_from=0):
    """One sequence tokens [T] -> (hidden [T, D], slack [L_r, T])."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        slack = []
        for lp in params["layers"]:
            x = _attention(x, lp, theta, eps, scale_dim, rotate_k, kv_norm,
                           q_norm, value_from)
            if "mlp" in lp:
                x = _dense(x, lp, eps)
            else:
                taken = None if routing is None else jnp.asarray(
                    routing[len(slack)], jnp.int32)
                x, sl = _routed(x, lp, taken, eps, top_k, scaling, norm_topk,
                                use_bias, bias_weighs, shared)
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
                     params["lm_head"], kw["eps"])


def routing_slack(params, tokens, routing, **kw):
    """float32 [L_r, T]: per decision of ``routing`` [L_r, T, k] (tokens
    [1, T]) this file's k-th largest selection score minus the smallest
    among the experts named, on the hidden states that taking the named
    experts gives."""
    _forced(tokens, routing)
    return _run(params, tokens[0], routing, **kw)[1]


def deficits(params, hidden_i, first, served, n, **kw):
    return _deficits(hidden_i, first, served, n, params["final_norm"],
                     params["lm_head"], kw["eps"])


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


def loss(params, tokens, **kw):
    """Mean next-token cross-entropy of tokens [B, T + 1]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lg = logits_at(params, tokens[:, :-1], jnp.arange(tokens.shape[1] - 1),
                   **kw)
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return float(jnp.mean(nll))
