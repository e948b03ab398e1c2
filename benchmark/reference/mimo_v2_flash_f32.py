"""Plain float32 reference of MiMo-V2-Flash (``model_type``
``mimo_v2_flash``), written from the layer equations in ISSUE 55, and
importing nothing of ray_tpu or of the adapter: float32 everywhere, every
matmul at precision "highest", no cache, no kernel, no ring: a window layer
is a band in a [T, T] mask and its sink one more column of the softmax that
weighs nothing. One sequence at a time, one KV head's scores for a block of
query rows at a time, one expert cast to float32 at a time. The parameter
pytree is DATA, in the layout the program keeps it: ``layers`` is a list;
every layer has ``attn`` (wq [D, H, hd], wk [D, Hkv, hd], wv [D, Hkv, vd],
wo [H, vd, D]; ``sink`` [H] float32 where the layer's softmax has one) and
two norms (``attn_norm``, ``ffn_norm``); a layer with ``mlp`` has a dense
SwiGLU and one with ``moe`` routes (router [D, E], bias [E], w_gate / w_up
[held, D, F], w_down [held, F, D]: the matrices of the experts ``held``
alone; no shared expert); an untied ``lm_head`` [D, V]. The widths and the
KV-head count of a layer are read off its arrays.

    a = rms(x, attn_norm);  q = a Wq, k = a Wk, v = value_scale * (a Wv)
    the first ``rotary`` lanes of every q and k head rotated, lanes
        (2i, 2i + 1) paired, by theta_full in a full layer (pattern[l] 0)
        and theta_window in a window layer (pattern[l] 1); the rest pass
    s_ij = q_i . k_j / sqrt(hd); full layer: j <= i; window layer:
        0 <= i - j < window
    window layer: p_ij = exp(s_ij) / (exp(sink_h) + sum_j' exp(s_ij'));
        full layer: the plain softmax
    o = sum_j p_ij v_j, query head h on KV head h // (H / Hkv)
    x <- x + o Wo
    b = rms(x, ffn_norm); dense: f = SwiGLU(b)
    routed: s = sigmoid(b Wr); chosen = top_k of s + bias; weights
        s_e / (sum of the chosen s + 1e-20); f = sum over the chosen e IN
        ``held`` of w_e SwiGLU_e(b): ONE CHIP'S SHARE (a chosen expert held
        elsewhere adds nothing here; the shares of a partition of the
        experts add up to the whole layer: there is no shared expert)
    x <- x + f;  logits = rms(x, final_norm) W_head

Departures from the published modelling code, each an ``assumed`` entry of
the configuration's file: no q / k norm; the rotation pairs lanes (2i,
2i + 1) and positions are not scaled; the window's edge is ``0 <= i - j <
window``; the value scale multiplies v before the weighted sum; the sinks
and the selection bias are seeded (the published ones are learned); the
routed sum is a share; the multi-token-prediction layers are left out.

Keywords (the adapter's ``reference_kwargs``): theta_full, theta_window,
eps, top_k, window, pattern, rotary, value_scale, held (lo, hi); and the
negative controls' overrides, each leaving one rule out or getting it
wrong: ``window`` itself (127 / 129), ``window_sink=False`` (the sink left
out of the window layers), ``full_sink=<logit>`` (a sink of that logit
added to every head of the full layers), ``value_scale=1.0``, ``rotary=192``
(every lane rotated), ``theta_full`` / ``theta_window`` swapped,
``use_bias=False``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_QUERY_ROWS = 512       # query rows one block of scores holds


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _rope(x, theta, rotary):
    """x [T, H, hd]: of its first ``rotary`` lanes, (2i, 2i + 1) rotated
    against each other by pos * theta^(-2i/rotary); the rest as they are."""
    t = x.shape[0]
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rotary:2], x[..., 1:rotary:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       axis=-1).reshape(x.shape[:-1] + (rotary,))
    return jnp.concatenate([turned, x[..., rotary:]], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "theta", "eps", "window", "rotary", "value_scale", "sink"))
def _attention(x, lp, theta, eps, window, rotary, value_scale, sink):
    """One sequence x [T, D] through a layer's mixer. ``window`` 0: a full
    layer. ``sink``: None (the plain softmax), True (the layer's own
    ``sink`` [H]) or a float (that logit for every head)."""
    a = lp["attn"]
    t = x.shape[0]
    n_heads, hd = a["wq"].shape[1:]
    n_kv, vd = a["wv"].shape[1:]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = jnp.einsum("td,dhk->thk", h, _f32(a["wq"]), precision=HIGHEST)
    k = jnp.einsum("td,dhk->thk", h, _f32(a["wk"]), precision=HIGHEST)
    v = jnp.einsum("td,dhk->thk", h, _f32(a["wv"]), precision=HIGHEST) \
        * value_scale
    q, k = _rope(q, theta, rotary), _rope(k, theta, rotary)
    if sink is None:
        sinks = None
    elif sink is True:
        sinks = _f32(a["sink"])
    else:
        sinks = jnp.full((n_heads,), sink, jnp.float32)
    # [Hkv, blocks, rep, rows, hd]: a KV head's query heads, a block of
    # query rows at a time
    rows = min(_QUERY_ROWS, t)
    pad = -t % rows
    rep = n_heads // n_kv
    qh = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        (t + pad) // rows, rows, n_kv, rep, hd).transpose(2, 0, 3, 1, 4)
    kpos = jnp.arange(t)

    def kv_head(args):
        qs, kh, vh, sk = args       # [blocks, rep, rows, hd], [T, hd], [rep]

        def block(args):
            qb, first = args
            qpos = first + jnp.arange(rows)
            seen = kpos[None, :] <= qpos[:, None]
            if window:
                seen &= qpos[:, None] - kpos[None, :] < window
            s = jnp.einsum("rqk,tk->rqt", qb, kh, precision=HIGHEST) \
                * hd ** -0.5
            s = jnp.where(seen[None], s, -jnp.inf)
            if sinks is not None:       # one more column, which weighs nothing
                s = jnp.concatenate([s, jnp.broadcast_to(
                    sk[:, None, None], (rep, rows, 1))], axis=-1)
            p = jax.nn.softmax(s, axis=-1)[..., :t]
            return jnp.einsum("rqt,tk->rqk", p, vh, precision=HIGHEST)

        return jax.lax.map(block, (qs, jnp.arange(qs.shape[0]) * rows))

    sk = jnp.zeros((n_kv, rep)) if sinks is None else sinks.reshape(n_kv, rep)
    o = jax.lax.map(kv_head, (qh, k.swapaxes(0, 1), v.swapaxes(0, 1), sk))
    # [Hkv, blocks, rep, rows, vd] -> [T, H, vd], kv-major heads
    o = o.transpose(1, 3, 0, 2, 4).reshape(t + pad, n_heads, vd)[:t]
    return x + jnp.einsum("thk,hkd->td", o, _f32(a["wo"]), precision=HIGHEST)


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
    "eps", "top_k", "held", "use_bias"))
def _routed(x, lp, taken, eps, top_k, held, use_bias):
    """x [T, D]; ``taken`` [T, k] the experts to take (of ALL the router's),
    or None for this file's own choice; ``held`` (lo, hi): the experts whose
    matrices ``lp`` has. Returns (x, slack [T]): per token the k-th largest
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
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    combine = jnp.sum(hot * w[..., None], axis=1)                # [T, E]

    def one(acc, e):                     # e: a held expert's own row
        y = _swiglu(g, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e])
        return acc + y * combine[:, held[0] + e][:, None], None

    f, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(held[1] - held[0]))
    return x + f, slack


def _vocab_blocks(lm_head):
    """(blocks, columns a block): the head is cast to float32 a block of
    columns at a time (whole it is 2.5 GB beside 9 GB of weights)."""
    v = lm_head.shape[1]
    n = next(n for n in range(1, v + 1) if v % n == 0 and v // n <= 16384)
    return n, v // n


def _logit_blocks(x, final_norm, lm_head, eps, fold):
    """``fold(logits [P, columns])`` over the head's blocks of columns,
    stacked along a leading axis."""
    xn = _rms_norm(x, final_norm, eps)
    n, width = _vocab_blocks(lm_head)

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(lm_head, i * width, width, axis=1)
        return fold(jnp.dot(xn, _f32(w), precision=HIGHEST))

    return jax.lax.map(block, jnp.arange(n))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, eps):
    """x [..., D] -> logits [..., V]."""
    rows = x.reshape(-1, x.shape[-1])
    lg = _logit_blocks(rows, final_norm, lm_head, eps, lambda b: b)
    return lg.swapaxes(0, 1).reshape(*x.shape[:-1], -1)


def _run(params, tokens, routing, *, theta_full, theta_window, eps, top_k,
         window, pattern, rotary, value_scale, held, window_sink=True,
         full_sink=None, use_bias=True):
    """One sequence tokens [T] -> (hidden [T, D], slack [L_r, T])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(
            jnp.float32)
        slack = []
        for i, lp in enumerate(params["layers"]):
            if pattern[i]:
                x = _attention(x, lp, theta_window, eps, window, rotary,
                               value_scale, True if window_sink else None)
            else:
                x = _attention(x, lp, theta_full, eps, 0, rotary,
                               value_scale, full_sink)
            if "mlp" in lp:
                x = _dense(x, lp, eps)
            else:
                taken = None if routing is None else jnp.asarray(
                    routing[len(slack)], jnp.int32)
                x, sl = _routed(x, lp, taken, eps, top_k, tuple(held),
                                use_bias)
                slack.append(sl)
        return x, (jnp.stack(slack) if slack
                   else jnp.zeros((0, x.shape[0]), jnp.float32))


def _forced(tokens, routing):
    if routing is not None and len(tokens) != 1:
        raise ValueError(
            "routing= names one sequence's experts: tokens [1, T]")
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
    """The largest logit a row, a block of the head's columns at a time
    (3,073 rows of 152,576 logits are 1.9 GB), and the served token's from
    its own column."""
    w = served.shape[0]
    with jax.default_matmul_precision("highest"):
        x = jax.lax.dynamic_slice_in_dim(hidden_i, first, w, axis=0)
        top, finite = _logit_blocks(
            x, final_norm, lm_head, eps, lambda b: (
                jnp.max(b, axis=-1), jnp.all(jnp.isfinite(b), axis=-1)))
        own = jnp.sum(_rms_norm(x, final_norm, eps)
                      * _f32(lm_head[:, served]).T, axis=-1)
    gap = jnp.max(top, axis=0) - own
    live = jnp.arange(w) < n
    return (jnp.max(jnp.where(live, gap, 0.0)),
            jnp.all(jnp.where(live, jnp.all(finite, axis=0), True)))


def loss(params, tokens, **kw):
    """Mean next-token cross-entropy of tokens [B, T + 1]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lg = logits_at(params, tokens[:, :-1], jnp.arange(tokens.shape[1] - 1),
                   **kw)
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return float(jnp.mean(nll))
