"""What the toy routed family's tests and its chip run share: the family
loaded from this directory, the strict comparison, the int8 control, and a
greedy decode through the toy's own paged programs (the harness has no
engine for the family, so check 2's "served" tokens are made here)."""

from __future__ import annotations

import os
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import common

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = common.load_json(HERE, "benchmark", "configs", "routed-toy.json")
FAM = common.load_module("models", CONFIG["model_family"], HERE)
# the same family without ``routing_taken``: check 1's routing code does not
# run, the reference routes by its own scores, and the comparison is the
# dense family's
STRICT = types.SimpleNamespace(**{k: v for k, v in vars(FAM).items()
                                  if k != "routing_taken"})


def int8_experts(params, per_channel: bool = False):
    """Every expert matrix rounded to an int8 grid: one scale a tensor, or
    (``per_channel``) one a column of each expert's matrix, the grid a
    weight-only int8 path would take."""
    def q(w):
        w32 = w.astype(jnp.float32)
        s = jnp.max(jnp.abs(w32), axis=-2 if per_channel else None,
                    keepdims=per_channel) / 127.0
        return (jnp.round(w32 / s).clip(-127, 127) * s).astype(w.dtype)
    layers = [dict(lp, moe=dict(lp["moe"], **{
        k: q(lp["moe"][k]) for k in ("w_gate", "w_up", "w_down")}))
        if "moe" in lp else lp for lp in params["layers"]]
    return dict(params, layers=layers)


def greedy(cfg, params, engine: dict, prompts: list[list[int]],
           max_tokens: int) -> list[list[int]]:
    """Each prompt prefilled into pages of its own (whole, or in chunks
    above ``prefill_chunk``, as an engine would), then all decoded
    together, greedily, for ``max_tokens`` steps."""
    page, cap = engine["page_size"], engine["max_prompt_len"]
    chunk = engine["prefill_chunk"]
    max_pages = -(-engine["max_seq_len"] // page)
    init_cache, prefill, chunk_fn, decode = FAM.paged_programs(
        cfg, page, FAM.attention_backend("auto", cfg, page))
    kv = init_cache(len(prompts) * max_pages + 1)
    tables = 1 + np.arange(len(prompts) * max_pages, dtype=np.int32).reshape(
        len(prompts), max_pages)

    def padded(seg, width):
        out = np.zeros((1, width), np.int32)
        out[0, :len(seg)] = seg
        return jnp.asarray(out)

    cur = []
    for table, p in zip(tables, prompts):
        n, start = len(p), 0
        if n <= chunk:
            lg, kv = prefill(params, kv, jnp.asarray(table),
                             padded(p, common.prefill_bucket(n, cap)),
                             jnp.int32(n))
        while n > chunk and start < n:
            width = chunk if n - start > chunk \
                else common.prefill_bucket(n - start, cap)
            lg, kv = chunk_fn(params, kv, jnp.asarray(table),
                              padded(p[start:start + width], width),
                              jnp.int32(start), jnp.int32(n))
            start += width
        cur.append(int(jnp.argmax(lg)))
    out = [[t] for t in cur]
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    for _ in range(max_tokens - 1):
        lg, kv, lens = decode(params, kv, jnp.asarray(tables), lens,
                              jnp.asarray(cur, jnp.int32))
        cur = [int(t) for t in np.asarray(jnp.argmax(lg, axis=-1))]
        for o, t in zip(out, cur):
            o.append(t)
    return out


def served_samples(cfg, params, engine: dict, seed: int, lengths: list[int],
                   max_tokens: int) -> list[dict]:
    """Check 2's samples: prompts of the given lengths drawn from the
    seed, with the tokens the toy's programs decode for them."""
    rng = np.random.default_rng(seed & 0xFFFFFFFF)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)]
               for n in lengths]
    return [{"prompt_ids": p, "tokens": t, "max_tokens": max_tokens}
            for p, t in zip(prompts, greedy(cfg, params, engine, prompts,
                                            max_tokens))]
