"""Plain float32 reference of SDAR-MoE (``model_type`` ``sdar_moe``), written
from the layer equations in ISSUE 37 / the published description of the
family, and importing nothing of ray_tpu or of the adapter: float32
everywhere, every matmul at precision "highest", no cache, no kernel, one
sequence at a time, one layer (and one expert) cast to float32 at a time.
The parameter pytree is DATA, in the layout the program keeps it: ``layers``
is a list, every layer alike: ``attn`` wq
[D, H, hd], wk / wv [D, Hkv, hd], wo [H, hd, D], q_norm / k_norm [hd];
``moe`` router [D, E], w_gate / w_up [E, D, F], w_down [E, F, D]; norms
``attn_norm`` and ``ffn_norm``; ``embed`` [V, D], ``final_norm``, ``lm_head``
[D, V] (the head is its own matrix).

    h = x + Attn(rms(x, attn_norm));  y = h + MoE(rms(h, ffn_norm))
    attn:  q = rope(rms(z W_q, q_norm)), k = rope(rms(z W_k, k_norm)) per
           head, the rotation on all lanes at the absolute position, pairing
           lanes (2i, 2i + 1); scores scaled by hd ** -0.5; query head h
           reads KV head h // (H / Hkv); W_o
    moe:   p = softmax(z W_r); chosen = the top_k most probable; weights
           p_e / sum of the chosen (``norm_topk``); sum_e w_e SwiGLU_e(z)

GENERATION IS BY DIFFUSION OVER BLOCKS of ``block`` B positions from
position 0, and a generation state is ONE forward over [the clean sequence ;
noisy copies of some of its blocks] under the block-diffusion mask, the
architecture's own training-time layout, which needs no cache: a clean
position sees the clean blocks up to and including its own; a noisy copy of
block g (its known tokens, the mask token ``mask`` elsewhere) sees the clean
blocks before g and itself. The logits AT a masked position are the
distribution of the token that belongs there (no shift by one); the mask
token's own logit is left out of every maximum (it is never produced: a
departure from the published sampler, stated in the configuration's
``assumed``). :func:`generate` is the family's own sampler on top of that
forward (``denoise`` passes a block, the ``ceil(B / denoise)`` masked
positions whose best token is most probable revealed a pass, greedy).

The contract of benchmark/reference/__init__.py, with THIS family's meaning
of each function:

``logits_at(params, tokens [1, T], positions, routing=...)``: for position t
    the logits at index t + 1 of the pass over the clean blocks before
    ``block(t + 1)`` and that block holding ``tokens`` up to t and the mask
    token after: what a denoise pass gives for the next position when a
    block is revealed left to right, one a pass. That order is check 1's own
    (it feeds one known token a call); it is a valid input of the same
    denoise program, whose reveal ORDER is held by check 2 and by the CPU
    tests instead.
``routing=`` int32 [L, T, 2 * B * k], the adapter's record, one row a
    position (-1 = nothing): columns ``[0, B * k)`` of row t, the B x k
    experts of the denoise pass that gave the logits for t + 1; columns
    ``[B * k, 2 * B * k)`` of the row of a block's LAST position, the B x k
    experts of the pass that committed that block (a prefill, a chunk, a
    commit pass). A clean position whose block was never committed takes
    this file's own choice. The combine weights are always this file's own
    float32 probabilities.
``routing_slack``: float32 [decisions]: one number for each (layer, row)
    decision the record holds (committed clean positions, and the B rows of
    every denoise pass), this file's k-th largest probability minus the
    smallest among the experts named, on the hidden states that taking the
    named experts gives.
``hidden`` (check 2, tokens only, this file's own routing) hands the streams
    on untouched: what has to be evaluated depends on where the served
    tokens begin, which only ``deficits`` is told.
``deficits(params, stream, first, served, n)``: a served block was revealed
    as the F positions of its first pass (the ``ceil(B / denoise)`` most
    confident of the positions masked at its start, M: what the prompt left
    in the block is known) from the state in which all of M is masked, and
    the rest of M given those. For every block that holds served tokens this
    evaluates that first state and one state for each F that M allows (B 4,
    two passes: 1 + 6 noisy copies of a block); a token's deficit (the
    largest logit - its own, the mask token left out) is taken in the state
    it would have been revealed from, a block's deficit is the SMALLEST over
    F of the largest of its tokens' deficits, a stream's the largest over
    its blocks. A near-tie between two positions' confidences that bfloat16
    turns over then costs nothing; a replaced token misses in every state. A
    stream that stopped short is checked on the stop token. The ONE block a
    stop or ``max_tokens`` cut is judged on the tokens it served; a position
    past the cut (revealed and discarded by the engine, so not known here)
    that a first set holds is given the first state's OWN best token there,
    which is what a sound engine revealed: exact unless rounding turned that
    very token over.

Keywords (the adapter's ``reference_kwargs``): theta, eps, top_k, block,
mask, denoise; and the negative controls' overrides, each leaving one rule
out: ``qk_norm=False``, ``norm_topk=False``, ``block_mask=False`` (causal
attention everywhere).
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_STATIC = ("theta", "eps", "top_k", "block", "norm_topk", "qk_norm",
           "block_mask")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _rope(x, pos, theta):
    """x [R, H, hd] at positions pos [R]: lanes (2i, 2i + 1) rotated
    against each other by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention(xc, xn, lp, blocks, theta, eps, block, qk_norm, block_mask):
    """Clean rows xc [T, D] and noisy blocks xn [N, B, D] (copy n stands
    for block ``blocks[n]``) through a layer's attention."""
    a = lp["attn"]
    t, (n, b, d) = xc.shape[0], xn.shape
    rows = jnp.concatenate([xc, xn.reshape(n * b, d)])
    pos_c = jnp.arange(t)
    pos_n = (blocks[:, None] * block + jnp.arange(b)[None, :]).reshape(-1)
    pos = jnp.concatenate([pos_c, pos_n])
    h = _rms_norm(rows, lp["attn_norm"], eps)
    q = jnp.einsum("td,dhk->thk", h, _f32(a["wq"]), precision=HIGHEST)
    k = jnp.einsum("td,dhk->thk", h, _f32(a["wk"]), precision=HIGHEST)
    v = jnp.einsum("td,dhk->thk", h, _f32(a["wv"]), precision=HIGHEST)
    if qk_norm:
        q, k = _rms_norm(q, a["q_norm"], eps), _rms_norm(k, a["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    hkv, hd = k.shape[1], k.shape[2]
    rep = q.shape[1] // hkv
    sm = hd ** -0.5
    if block_mask:
        see_cc = pos_c[None, :] // block <= pos_c[:, None] // block
        see_nn = jnp.ones((b, b), bool)
    else:                      # the control: causal everywhere
        see_cc = pos_c[None, :] <= pos_c[:, None]
        see_nn = jnp.tril(jnp.ones((b, b), bool))
    see_nc = pos_c[None, :] // block < pos_n[:, None] // block   # [N*B, T]

    def group(args):
        """One KV head and its ``rep`` query heads (a head group at a
        time keeps the scores of a long stream's noisy copies small)."""
        qg, kg, vg = args                    # [rep, R, hd], [R, hd], [R, hd]
        s_cc = jnp.einsum("rqd,kd->rqk", qg[:, :t], kg[:t],
                          precision=HIGHEST) * sm
        o_c = jnp.einsum("rqk,kd->rqd", jax.nn.softmax(
            jnp.where(see_cc[None], s_cc, -jnp.inf), axis=-1), vg[:t],
            precision=HIGHEST)
        qn = qg[:, t:].reshape(rep, n, b, hd)
        kn, vn = kg[t:].reshape(n, b, hd), vg[t:].reshape(n, b, hd)
        s_nc = jnp.einsum("rqd,kd->rqk", qg[:, t:], kg[:t],
                          precision=HIGHEST) * sm                # [rep,NB,T]
        s_nn = jnp.einsum("rnqd,nkd->rnqk", qn, kn, precision=HIGHEST) * sm
        s = jnp.concatenate([
            jnp.where(see_nc[None], s_nc, -jnp.inf).reshape(rep, n, b, t),
            jnp.where(see_nn[None, None], s_nn, -jnp.inf)], axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        o_n = jnp.einsum("rnqk,kd->rnqd", p[..., :t], vg[:t],
                         precision=HIGHEST) \
            + jnp.einsum("rnqk,nkd->rnqd", p[..., t:], vn, precision=HIGHEST)
        return jnp.concatenate([o_c, o_n.reshape(rep, n * b, hd)], axis=1)

    qg = q.reshape(-1, hkv, rep, hd).transpose(1, 2, 0, 3)
    o = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(2, 0, 1, 3).reshape(-1, hkv * rep, hd)       # [R, H, hd]
    out = rows + jnp.einsum("thk,hkd->td", o, _f32(a["wo"]),
                            precision=HIGHEST)
    return out[:t], out[t:].reshape(n, b, d)


def _swiglu(g, w_gate, w_up, w_down):
    gate = jax.nn.silu(jnp.dot(g, _f32(w_gate), precision=HIGHEST))
    return jnp.dot(gate * jnp.dot(g, _f32(w_up), precision=HIGHEST),
                   _f32(w_down), precision=HIGHEST)


def _routed(x, lp, taken, eps, top_k, norm_topk):
    """x [R, D]; ``taken`` [R, k] the experts to take (a row of -1: this
    file's own choice). Returns (x, slack [R]): per row the k-th largest
    probability minus the smallest among the taken (0 for the own choice;
    inf for an expert named twice or not there)."""
    moe = lp["moe"]
    g = _rms_norm(x, lp["ffn_norm"], eps)
    p = jax.nn.softmax(jnp.dot(g, _f32(moe["router"]), precision=HIGHEST),
                       axis=-1)
    n_experts = p.shape[-1]
    kth = jnp.sort(p, axis=-1)[:, n_experts - top_k]
    own = jax.lax.top_k(p, top_k)[1]
    taken = jnp.where(taken[:, :1] < 0, own, taken)
    there = (taken >= 0) & (taken < n_experts)
    ids = jnp.clip(taken, 0, n_experts - 1)
    hot = jax.nn.one_hot(ids, n_experts, dtype=jnp.float32)      # [R, k, E]
    sound = jnp.all(there, axis=-1) & jnp.all(jnp.sum(hot, axis=1) <= 1.0,
                                              axis=-1)
    w = jnp.take_along_axis(p, ids, axis=-1)
    slack = jnp.where(sound, kth - jnp.min(w, axis=-1), jnp.inf)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    combine = jnp.sum(hot * w[..., None], axis=1)                # [R, E]

    def one(acc, e):
        y = _swiglu(g, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e])
        return acc + y * combine[:, e][:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_experts))
    return x + y, slack


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer(xc, xn, lp, blocks, taken_c, taken_n, *, theta, eps, top_k, block,
           norm_topk, qk_norm, block_mask):
    xc, xn = _attention(xc, xn, lp, blocks, theta, eps, block, qk_norm,
                        block_mask)
    t, (n, b, d) = xc.shape[0], xn.shape
    rows, slack = _routed(
        jnp.concatenate([xc, xn.reshape(n * b, d)]), lp,
        jnp.concatenate([taken_c, taken_n.reshape(n * b, -1)]), eps, top_k,
        norm_topk)
    return rows[:t], rows[t:].reshape(n, b, d), slack[:t], \
        slack[t:].reshape(n, b)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def _forward(params, clean, noisy, blocks, taken_c=None, taken_n=None, *,
             theta, eps, top_k, block, mask=None, denoise=None,
             norm_topk=True, qk_norm=True, block_mask=True):
    """One forward over [clean [T] ; noisy [N, B] standing for the blocks
    ``blocks`` [N]]. ``taken_c`` [L, T, k] / ``taken_n`` [L, N, B, k]: the
    experts to take (None or -1 = the own choice). Returns (clean hidden
    [T, D], noisy hidden [N, B, D], slack [L, T], slack [L, N, B])."""
    del mask, denoise                    # the callers' (they build ``noisy``)
    clean, noisy = jnp.asarray(clean, jnp.int32), jnp.asarray(noisy, jnp.int32)
    blocks = jnp.asarray(blocks, jnp.int32)
    free_c = jnp.full((clean.shape[0], top_k), -1, jnp.int32)
    free_n = jnp.full(noisy.shape + (top_k,), -1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        xc, xn = _embed(params["embed"], clean), _embed(params["embed"], noisy)
        slack_c, slack_n = [], []
        for l, lp in enumerate(params["layers"]):
            xc, xn, sc, sn = _layer(
                xc, xn, lp, blocks,
                free_c if taken_c is None else jnp.asarray(taken_c[l]),
                free_n if taken_n is None else jnp.asarray(taken_n[l]),
                theta=theta, eps=eps, top_k=top_k, block=block,
                norm_topk=norm_topk, qk_norm=qk_norm, block_mask=block_mask)
            slack_c.append(sc)
            slack_n.append(sn)
    return xc, xn, jnp.stack(slack_c), jnp.stack(slack_n)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, eps):
    return jnp.dot(_rms_norm(x, final_norm, eps), _f32(lm_head),
                   precision=HIGHEST)


# ---- check 1 ------------------------------------------------------------------

def _left_to_right(tokens, positions, block, mask):
    """The noisy blocks ``logits_at`` evaluates: for position t the block
    of t + 1 holding ``tokens`` up to t, the mask token after."""
    tokens = np.asarray(tokens)
    pad = np.concatenate([tokens, np.zeros((2 * block,), tokens.dtype)])
    blocks = np.asarray([(t + 1) // block for t in positions], np.int32)
    at = blocks[:, None] * block + np.arange(block)[None, :]
    known = at <= np.asarray(positions)[:, None]
    return np.where(known, pad[at], mask).astype(np.int32), blocks


def _forced(tokens, routing, positions, block, top_k):
    """The adapter's record [L, T, 2 * B * k] (module docstring) as the
    experts of the clean rows [L, T, k] and of the noisy blocks [L, N, B,
    k], and which clean rows it names."""
    if len(tokens) != 1:
        raise ValueError("routing= names one sequence's experts: tokens [1, T]")
    routing = np.asarray(routing, np.int32)
    n_layers, t, width = routing.shape
    if width != 2 * block * top_k:
        raise ValueError(f"a routing record of {2 * block * top_k} columns "
                         f"a position, got {width}")
    rows = np.arange(t)
    last = np.minimum(rows // block * block + block - 1, t - 1)
    commit = routing[:, last, block * top_k:].reshape(
        n_layers, t, block, top_k)
    taken_c = commit[:, rows, rows % block]
    # a block whose last position lies past the record was never committed
    taken_c = np.where((rows // block * block + block - 1 < t)[None, :, None],
                       taken_c, -1)
    taken_n = routing[:, np.asarray(positions), :block * top_k].reshape(
        n_layers, len(positions), block, top_k)
    return taken_c, taken_n, taken_c[0, :, 0] >= 0


def logits_at(params, tokens, positions, *, routing=None, **kw):
    """Logits [B, len(positions), V] (module docstring); with ``routing``
    (B = 1) through the experts named there."""
    positions = [int(p) for p in np.asarray(positions)]
    out = []
    for seq in np.asarray(tokens):
        noisy, blocks = _left_to_right(seq, positions, kw["block"],
                                       kw["mask"])
        taken = (None, None) if routing is None else _forced(
            tokens, routing, positions, kw["block"], kw["top_k"])[:2]
        _, xn, _, _ = _forward(params, seq, noisy, blocks, *taken, **kw)
        at = jnp.asarray([(p + 1) % kw["block"] for p in positions])
        with jax.default_matmul_precision("highest"):
            out.append(_head(xn[jnp.arange(len(positions)), at],
                             params["final_norm"], params["lm_head"],
                             kw["eps"]))
    return jnp.stack(out)


def routing_slack(params, tokens, routing, **kw):
    """float32 [decisions] (module docstring): the denoise passes are the
    rows whose first columns are filled."""
    positions = [int(p) for p in np.nonzero(
        np.asarray(routing)[0, :, 0] >= 0)[0]]
    noisy, blocks = _left_to_right(np.asarray(tokens)[0], positions,
                                   kw["block"], kw["mask"])
    taken_c, taken_n, named = _forced(tokens, routing, positions,
                                      kw["block"], kw["top_k"])
    _, _, slack_c, slack_n = _forward(params, np.asarray(tokens)[0], noisy,
                                      blocks, taken_c, taken_n, **kw)
    return np.concatenate([np.asarray(slack_c)[:, named].ravel(),
                           np.asarray(slack_n).ravel()])


# ---- check 2 ------------------------------------------------------------------

def hidden(params, tokens, *, routing=None, **kw):
    """The streams themselves, one a row (module docstring)."""
    if routing is not None:
        raise ValueError("check 2 goes through this file's own routing")
    return np.asarray(tokens, np.int32)


def first_sets(block: int, denoise: int) -> list[tuple]:
    """The sets a block's first pass may reveal when all of it is masked."""
    return list(itertools.combinations(range(block), -(-block // denoise)))


@functools.partial(jax.jit, static_argnames=("eps", "mask"))
def _gaps(xn, tokens, final_norm, lm_head, eps, mask):
    """Per noisy row the largest logit (the mask token left out) minus the
    logit of ``tokens``, the token that has it, and whether every logit is
    finite; a block at a time (a stream's rows x the vocabulary would not
    fit)."""
    def one(args):
        x, tok = args
        lg = _head(x, final_norm, lm_head, eps)                   # [S, B, V]
        own = jnp.where(jnp.arange(lg.shape[-1]) == mask, -jnp.inf, lg)
        return (jnp.max(own, axis=-1) - jnp.take_along_axis(
                    lg, tok[..., None], axis=-1)[..., 0],
                jnp.argmax(own, axis=-1), jnp.all(jnp.isfinite(lg), axis=-1))
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (xn, tokens))


def deficits(params, stream, first, served, n, **kw):
    """(the stream's deficit, whether every logit read is finite) for the n
    tokens served from position first + 1 on (module docstring). ``stream``
    is a row of ``hidden``; ``served`` only fixes the width every run of a
    cell evaluates."""
    block, mask = kw["block"], kw["mask"]
    sets = first_sets(block, kw["denoise"])
    stream, plen, n = np.asarray(stream), int(first) + 1, int(n)
    width = len(served) // block + 2         # blocks that may hold a token
    blocks = plen // block + np.arange(width, dtype=np.int32)
    at = blocks[:, None] * block + np.arange(block)[None, :]     # [W, B]
    pad = np.concatenate([stream, np.zeros(((width + 1) * block,), np.int32)])
    toks = pad[at]
    prompt, judged = at < plen, (at >= plen) & (at < plen + n)

    def gaps(noisy):
        """[W, S, B] noisy blocks -> (gap, best token, finite), each
        [W, S, B], the gap that of the stream's own token."""
        states = noisy.shape[1]
        _, xn, _, _ = _forward(params, stream, noisy.reshape(-1, block),
                               np.repeat(blocks, states), **kw)
        return [np.asarray(a) for a in _gaps(
            xn.reshape(width, states, block, -1),
            jnp.asarray(np.broadcast_to(toks[:, None, :], noisy.shape)),
            params["final_norm"], params["lm_head"], kw["eps"], mask)]

    # the first state: what the prompt left. Then one state for each first
    # set: its served positions as served, its positions past a cut as the
    # first state's own best token (what a sound engine revealed there)
    gap0, best0, fin0 = gaps(np.where(prompt, toks, mask)[:, None, :])
    filled = np.where(prompt | judged, toks, best0[:, 0])
    early = np.stack([np.isin(np.arange(block), f) for f in sets])  # [S, B]
    gap1, _, fin1 = gaps(np.where(prompt[:, None, :] | early[None], filled[
        :, None, :], mask).astype(np.int32))
    worst, ok = 0.0, True
    for w in range(width):
        if not judged[w].any():
            continue
        ok &= bool(fin0[w, 0][judged[w]].all() and fin1[w][:, judged[w]].all())
        masked = ~prompt[w]
        fits = [s for s, f in enumerate(sets) if masked[list(f)].all()]
        if masked.sum() <= len(sets[0]) or not fits:
            # one pass reveals all of it: every token from the first state
            worst = max(worst, float(gap0[w, 0][judged[w]].max()))
            continue
        worst = max(worst, float(min(
            np.where(early[s], gap0[w, 0], gap1[w, s])[judged[w]].max()
            for s in fits)))
    return worst, ok


# ---- the family's own sampler (the CPU tests' yardstick) -------------------------

def generate(params, prompt, max_tokens: int, *, stop=None, **kw):
    """Greedy generation by diffusion over blocks, by repeated forwards and
    no cache: tokens served for ``prompt`` (what lies past ``max_tokens``
    or from a ``stop`` token on is revealed and dropped, as a deployment
    does). Returns (tokens, trace): ``trace`` one entry a denoise pass,
    (block, the block before the pass, the positions it revealed)."""
    block, mask, denoise = kw["block"], kw["mask"], kw["denoise"]
    reveal = -(-block // denoise)
    seq, out, trace = [int(t) for t in prompt], [], []
    while len(out) < max_tokens:
        g = len(seq) // block
        blk = np.full((block,), mask, np.int32)
        blk[:len(seq) % block] = seq[g * block:]
        clean = np.asarray(seq[:g * block] + [0] * block, np.int32)
        for _ in range(denoise):
            _, xn, _, _ = _forward(params, clean, blk[None],
                                   np.asarray([g], np.int32), **kw)
            with jax.default_matmul_precision("highest"):
                lg = np.array(_head(xn[0], params["final_norm"],
                                    params["lm_head"], kw["eps"]))
            lg[:, mask] = -np.inf
            tok = lg.argmax(-1)
            conf = 1.0 / np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)
            conf = np.where(blk == mask, conf, -1.0)
            take = [i for i in np.argsort(-conf, kind="stable")[:reveal]
                    if blk[i] == mask]
            trace.append((g, blk.copy(), take))
            blk[take] = tok[take]
        new = [int(t) for t in blk[len(seq) % block:]]
        seq = seq[:g * block] + [int(t) for t in blk]
        for t in new:
            if len(out) >= max_tokens or (stop is not None and t == stop):
                return out, trace
            out.append(t)
    return out, trace


def loss(params, tokens, **kw):
    raise NotImplementedError(
        "sdar_moe is served, not trained here: its training loss is over "
        "noised blocks under a schedule the catalog does not give")
