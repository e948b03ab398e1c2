"""A toy family with routed experts, for the benchmark's own tests: the
adapter AND the programs (the harness has no engine for it, so the four
paged programs are plain ``jax.numpy`` in the configuration's dtype,
written here). Dropped into a copy of benchmark/ it is a second model
family with no edit of a file that is there (test_routed_family.py);
nothing of it is imported by benchmark/.

The block (pre-norm, RMSNorm, residual): every layer mixes positions by
causal grouped-query attention with rotary embeddings (halves rotated
against each other) through a paged KV pool; layers 0 .. n_dense-1 then
take a dense SwiGLU, the others ``n_experts`` SwiGLU experts of which each
token takes ``top_k``: scores ``s = sigmoid(g W_r)``, the choice is the
top_k of ``s + b`` (a selection bias that the weights do not see), the
weights are ``s_i / (sum over the chosen s + 1e-6) * scaling``. Embedding
and output head are tied. So an expert layer lies below position-mixing
layers: a choice turned over at one position moves the logits of later ones.

``sizes()["fault"]`` switches one fault into the PROGRAMS (never the
reference), for the negative controls: ``wrong_expert`` (at position
FAULT_POS of the first layer that routes, the last expert chosen is
replaced by the one ranked last), ``no_norm`` (the weights are left
unnormalised), ``softmax`` (softmax scores where sigmoid is published).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

REFERENCE = "routed_toy_f32"
MODEL_SCOPES = ("embed", "norm", "attn", "mlp", "experts", "lm_head")
FAULT_POS = 3
FAULTS = ("", "wrong_expert", "no_norm", "softmax")


# hashable: the programs are cached and jitted on it
ToyConfig = collections.namedtuple("ToyConfig", (
    "vocab_size", "dim", "n_layers", "n_dense", "n_heads", "n_kv_heads",
    "head_dim", "ffn_dim", "n_experts", "top_k", "expert_dim", "max_seq_len",
    "rope_theta", "norm_eps", "scaling", "dtype", "fault"), defaults=("",))


def sizes(config: dict, rehearsal: bool) -> dict:
    return dict((config["rehearsal"] if rehearsal else config)["model"])


def model_config(sz: dict, n_layers: int | None = None, trainer=None):
    if sz.get("fault", "") not in FAULTS:
        raise ValueError(f"no fault {sz['fault']!r}: one of {FAULTS}")
    cfg = ToyConfig(**{k: sz[k] for k in ToyConfig._fields if k in sz})
    return cfg._replace(n_layers=n_layers or cfg.n_layers)


def reference_kwargs(cfg, **override) -> dict:
    return {"theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
            "top_k": cfg.top_k, "scaling": float(cfg.scaling), **override}


def attention_backend(kind, cfg, page: int) -> str:
    """Heads of 64 and plain jax.numpy: there is no kernel to resolve to."""
    return "gather"


@functools.partial(jax.jit, static_argnums=1)
def init_params(key, cfg):
    """Normal, std 1/sqrt(fan_in), in the served dtype; the selection bias
    (float32, as the router's scores are) normal with std 0.1."""
    dt = jnp.dtype(cfg.dtype)

    def w(k, *shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    d, h, hkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(key, cfg.n_layers + 1)
    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i], 9)
        lp = {"attn_norm": jnp.ones((d,), dt), "ffn_norm": jnp.ones((d,), dt),
              "wq": w(k[0], d, h, hd, fan_in=d),
              "wk": w(k[1], d, hkv, hd, fan_in=d),
              "wv": w(k[2], d, hkv, hd, fan_in=d),
              "wo": w(k[3], h, hd, d, fan_in=h * hd)}
        if i < cfg.n_dense:
            f = cfg.ffn_dim
            lp["mlp"] = {"w_gate": w(k[4], d, f, fan_in=d),
                         "w_up": w(k[5], d, f, fan_in=d),
                         "w_down": w(k[6], f, d, fan_in=f)}
        else:
            e, f = cfg.n_experts, cfg.expert_dim
            lp["moe"] = {"router": w(k[7], d, e, fan_in=d),
                         "bias": 0.1 * jax.random.normal(k[8], (e,),
                                                         jnp.float32),
                         "w_gate": w(k[4], e, d, f, fan_in=d),
                         "w_up": w(k[5], e, d, f, fan_in=d),
                         "w_down": w(k[6], e, f, d, fan_in=f)}
        layers.append(lp)
    return {"embed": w(keys[-1], cfg.vocab_size, d, fan_in=d),
            "layers": layers, "final_norm": jnp.ones((d,), dt)}


# ---- the programs -------------------------------------------------------------

def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def _rope(x, pos, theta):
    """x [B, R, H, hd], pos [B, R]: the two halves rotated against each
    other by pos * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _route(g, moe, pos, first_routed: bool, cfg):
    """g [N, D] -> (experts [N, k] int32, weights [N, k] float32)."""
    logits = jnp.dot(g, moe["router"], preferred_element_type=jnp.float32)
    s = (jax.nn.softmax(logits, axis=-1) if cfg.fault == "softmax"
         else jax.nn.sigmoid(logits))
    sel = s + moe["bias"]
    _, idx = jax.lax.top_k(sel, cfg.top_k)
    if cfg.fault == "wrong_expert" and first_routed:
        worst = jnp.argmin(sel, axis=-1).astype(idx.dtype)
        idx = idx.at[:, -1].set(
            jnp.where(pos == FAULT_POS, worst, idx[:, -1]))
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.fault != "no_norm":
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return idx.astype(jnp.int32), w * cfg.scaling


def _experts(g, moe, idx, w, cfg):
    """Every expert on every row, then the chosen ones combined: plain,
    and wasteful, as a toy may be."""
    gate = jax.nn.silu(jnp.einsum("nd,edf->enf", g, moe["w_gate"]))
    up = jnp.einsum("nd,edf->enf", g, moe["w_up"])
    out = jnp.einsum("enf,efd->end", gate * up, moe["w_down"])
    combine = jnp.sum(jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)
                      * w[..., None], axis=1)                    # [N, E]
    return jnp.einsum("end,ne->nd", out, combine.astype(out.dtype))


def _forward(params, cache, tables, tokens, pos, live, page: int, cfg):
    """tokens, pos, live [B, R]; tables [B, P]: row (b, r) is the token at
    position pos[b, r] of the sequence whose pages tables[b] lists. Each
    layer writes the rows' keys and values into the pool (rows that are
    not live go to the trash page 0), then every row attends to its
    sequence's cached positions up to its own. Returns (hidden [B, R, D],
    cache); the cache's ``routing`` holds the experts the rows chose, rows
    in (b, r) order."""
    b, r = tokens.shape
    n_rep = cfg.n_heads // cfg.n_kv_heads
    sm = cfg.head_dim ** -0.5
    page_idx = jnp.where(
        live, jnp.take_along_axis(tables, pos // page, axis=1), 0)
    offset = pos % page
    kpos = jnp.arange(tables.shape[1] * page)
    mask = kpos[None, None, :] <= pos[:, :, None]                 # [B, R, L]
    k_pool, v_pool, taken = cache["k"], cache["v"], cache["routing"]
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routed = 0
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope("attn"):
            h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            q = _rope(jnp.einsum("brd,dhk->brhk", h, lp["wq"]), pos,
                      cfg.rope_theta)
            k = _rope(jnp.einsum("brd,dhk->brhk", h, lp["wk"]), pos,
                      cfg.rope_theta)
            v = jnp.einsum("brd,dhk->brhk", h, lp["wv"])
            k_pool = k_pool.at[i, page_idx, offset].set(k)
            v_pool = v_pool.at[i, page_idx, offset].set(v)
            ks = k_pool[i][tables].reshape(b, -1, cfg.n_kv_heads, cfg.head_dim)
            vs = v_pool[i][tables].reshape(b, -1, cfg.n_kv_heads, cfg.head_dim)
            ks, vs = (jnp.repeat(a, n_rep, axis=2) for a in (ks, vs))
            s = jnp.einsum("brhk,blhk->bhrl", q, ks,
                           preferred_element_type=jnp.float32) * sm
            s = jnp.where(mask[:, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            a = jnp.einsum("bhrl,blhk->brhk", p, vs)
            x = x + jnp.einsum("brhk,hkd->brd", a, lp["wo"])
        g = _rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        if "mlp" in lp:
            with jax.named_scope("mlp"):
                m = lp["mlp"]
                x = x + jnp.dot(jax.nn.silu(jnp.dot(g, m["w_gate"]))
                                * jnp.dot(g, m["w_up"]), m["w_down"])
        else:
            with jax.named_scope("experts"):
                flat = g.reshape(b * r, -1)
                idx, w = _route(flat, lp["moe"], pos.reshape(-1),
                                routed == 0, cfg)
                x = x + _experts(flat, lp["moe"], idx, w, cfg).reshape(x.shape)
                taken = taken.at[routed, :b * r].set(idx)
                routed += 1
    return x, {"k": k_pool, "v": v_pool, "routing": taken}


def _logits(x, params, cfg):
    h = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.dot(h, params["embed"].T, preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=8)
def paged_programs(cfg, page: int, backend: str):
    """The four programs behind the signatures benchmark/checks.py drives
    (the contract is in the docstring of the family that benchmark/models/ has)."""
    dt = jnp.dtype(cfg.dtype)

    def init_cache(n_pages: int):
        pool = (cfg.n_layers, n_pages, page, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(pool, dt), "v": jnp.zeros(pool, dt),
                "routing": jnp.zeros((cfg.n_layers - cfg.n_dense,
                                      cfg.max_seq_len, cfg.top_k), jnp.int32)}

    def chunk(params, cache, table, tokens, start, total):
        pos = start + jnp.arange(tokens.shape[1])[None]
        x, cache = _forward(params, cache, table[None], tokens, pos,
                            pos < total, page, cfg)
        last = jnp.clip(total - 1 - start, 0, tokens.shape[1] - 1)
        return _logits(x[0, last], params, cfg), cache

    def prefill(params, cache, table, tokens, n):
        return chunk(params, cache, table, tokens, jnp.int32(0), n)

    def decode(params, cache, tables, lens, tokens):
        x, cache = _forward(params, cache, tables, tokens[:, None],
                            lens[:, None], jnp.ones((len(lens), 1), bool),
                            page, cfg)
        return _logits(x[:, 0], params, cfg), cache, lens + 1

    return init_cache, jax.jit(prefill), jax.jit(chunk), jax.jit(decode)


def routing_taken(cache):
    """int32 [L_r, rows, k]: the experts the last call's rows chose (rows
    beyond that call's hold an earlier call's)."""
    return cache["routing"]
