"""SDAR-MoE (``model_type`` ``sdar_moe``: JetLM/SDAR-30B-A3B-Chat), as the
harness knows it. The contract is the docstring of benchmark/models/
llama.py; this family routes (``routing_taken``) and GENERATES BY DIFFUSION
OVER BLOCKS: a step of its engine program yields a block of B tokens after
several passes, a prefill yields none, and the logits AT a masked position
are the distribution of the token that belongs there. Check 1 drives one
known token a ``decode`` call and reads one routing decision a position;
``paged_programs`` here maps that protocol onto the engine's own jitted
programs (kv_cache.paged_prefill / paged_prefill_chunk / paged_block_step,
no second implementation of any pass) in plain Python around them:

    prefill / chunk  the engine's program (the prompt's whole blocks
                     committed); on the call that ends the prompt, the
                     pending block (what the prompt left, then the mask
                     token) and one DENOISE pass over it: the logits at the
                     index of position ``n`` (the next one)
    decode           ``cur`` goes into the slot's pending block; a block it
                     fills is COMMITTED (one commit pass, the other slots'
                     tables zeroed so that they write the trash page) and a
                     fresh all-masked block follows; then one denoise pass
                     over the block that holds position ``lens + 1``, and
                     its logits there

So check 1 reveals a block left to right, one token a pass: a valid input
of the same denoise program (the reveal ORDER of real generation is held by
check 2 and the CPU tests; benchmark/reference/sdar_moe_f32.py says how the
reference reads each function). The cache is this file's pytree: the
engine's cache, every sequence's pending block (by its first page, as slot
state rides the page table) and the routing record.

``routing_taken`` returns int32 [L, rows, 2 * B * k], one row a position of
the last call (-1 = nothing): columns ``[0, B * k)`` the B x k experts of the
denoise pass the call ended with, in the row of the call's LAST position;
columns ``[B * k, 2 * B * k)`` the B x k experts of the pass that committed a
block (prefill, chunk or commit pass), in the row of that block's last
position. checks.py only slices rows and joins them along positions.

The program is ray_tpu/models/sdar_moe.py through the engine's paged
programs; the plain reference benchmark/reference/sdar_moe_f32.py.
"""

from __future__ import annotations

import functools

REFERENCE = "sdar_moe_f32"
MODEL_SCOPES = ("embed", "norm", "attn", "router", "experts", "lm_head",
                "unmask", "sample")


def sizes(config: dict, rehearsal: bool) -> dict:
    """Model sizes under the program's names, from the published keys (or
    the rehearsal's tiny preset). Every layer attends and routes:
    ``attn_layers`` = ``n_layers``, ``n_dense`` 0 (the readers of the
    grouped product subtract it)."""
    if rehearsal:
        sz = dict(config["rehearsal"]["model"])
    else:
        gen = config["assumed"]["generation"]
        sz = {"vocab_size": config["vocab_size"],
              "dim": config["hidden_size"],
              "n_layers": config["num_hidden_layers"],
              "n_heads": config["num_attention_heads"],
              "n_kv_heads": config["num_key_value_heads"],
              "head_dim": config["head_dim"],
              "n_experts": config["num_experts"],
              "top_k": config["num_experts_per_tok"],
              "expert_dim": config["moe_intermediate_size"],
              "max_seq_len": config["engine"]["max_seq_len"],
              "rope_theta": float(config["rope_theta"]),
              "norm_eps": config["rms_norm_eps"],
              "norm_topk_prob": config["norm_topk_prob"],
              "block_length": gen["block_length"],
              "mask_token_id": gen["mask_token_id"],
              "denoise_passes": gen["denoise_passes"],
              "dtype": "bfloat16"}
    sz["attn_layers"], sz["n_dense"] = sz["n_layers"], 0
    return sz


def model_config(sz: dict, n_layers: int | None = None, trainer=None):
    """``n_layers=depth``: the first ``depth`` layers (check 1's model)."""
    import jax.numpy as jnp

    from ray_tpu.models import sdar_moe
    keys = ("vocab_size", "dim", "n_heads", "n_kv_heads", "head_dim",
            "n_experts", "top_k", "expert_dim", "max_seq_len", "rope_theta",
            "norm_eps", "norm_topk_prob", "block_length", "mask_token_id",
            "denoise_passes")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[sz["dtype"]]
    return sdar_moe.SdarMoeConfig(
        n_layers=n_layers or sz["n_layers"], dtype=dtype,
        **{k: sz[k] for k in keys if k in sz})


def init_params(key, cfg):
    """The model module's jitted initialiser itself: check 2's rebuild of
    the served weights is the engine's program, so equal to the bit."""
    from ray_tpu.models import sdar_moe
    return sdar_moe.init_params(key, cfg)


def attention_backend(kind, cfg, page: int) -> str:
    from ray_tpu.serve.llm import kv_cache as kvc
    return kvc.resolve_attention_backend(kind, cfg, page)


@functools.lru_cache(maxsize=8)
def paged_programs(cfg, page: int, backend: str):
    """Check 1's four programs (module docstring), around the engine's
    paged programs jitted once per shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm import kv_cache as kvc

    b, k, mask = cfg.block_length, cfg.top_k, cfg.mask_token_id
    width = 2 * b * k
    prefill_p = jax.jit(lambda p, kv, t, x, n: kvc.paged_prefill(
        p, kv, t, x, n, cfg, page)[1])
    chunk_p = jax.jit(lambda p, kv, t, x, s, n: kvc.paged_prefill_chunk(
        p, kv, t, x, s, n, cfg, page, backend)[1])
    denoise_p = jax.jit(lambda p, kv, t, sl, x: kvc.paged_block_step(
        p, kv, t, sl, x, cfg, page, backend, commit=False)[:2])
    commit_p = jax.jit(lambda p, kv, t, sl, x: kvc.paged_block_step(
        p, kv, t, sl, x, cfg, page, backend, commit=True)[1])

    def init_cache(n_pages):
        return {"kv": kvc.init_paged_cache(cfg, n_pages, page),
                "pending": np.full((n_pages, b), mask, np.int32),
                "rows": np.full((cfg.n_layers, 1, width), -1, np.int32)}

    def chosen(kv, rows):
        """The last pass's experts, [L, rows, k]."""
        return np.asarray(kv["routing"][:, :rows])

    def committed(rows, chose, first, n_blocks):
        """``chose`` [L, n_blocks * B, k] into the commit columns of the
        rows of the blocks' last positions, from row ``first`` on."""
        at = first + b * np.arange(n_blocks) + b - 1
        rows[:, at, b * k:] = chose.reshape(cfg.n_layers, n_blocks, b * k)

    def denoise(params, kv, tables, lens, blocks, at):
        """One denoise pass; the logits at index ``at`` [W] of each block,
        and the pass's experts [L, W, B * k]."""
        logits, kv = denoise_p(params, kv, jnp.asarray(tables),
                               jnp.asarray(lens, jnp.int32),
                               jnp.asarray(blocks))
        w = len(lens)
        return logits[jnp.arange(w), jnp.asarray(at)], kv, chosen(
            kv, w * b).reshape(cfg.n_layers, w, b * k)

    def prompt_pass(cache, params, kv, table, tokens, start, total):
        """What prefill and chunk share once the engine's program ran over
        ``tokens`` [1, C] from position ``start``."""
        c = tokens.shape[1]
        kept = total - total % b
        rows = np.full((cfg.n_layers, c, width), -1, np.int32)
        n_blocks = max(0, min(kept, start + c) - start) // b
        if n_blocks:
            committed(rows, chosen(kv, n_blocks * b), 0, n_blocks)
        cache = {**cache, "rows": rows}
        if start + c < total:                 # the prompt goes on
            return jnp.zeros((cfg.vocab_size,), jnp.float32), {**cache,
                                                               "kv": kv}
        block = np.full((b,), mask, np.int32)
        block[:total - kept] = np.asarray(tokens)[0, kept - start:
                                                  total - start]
        table = np.asarray(table)
        logits, kv, took = denoise(params, kv, table[None], [kept],
                                   block[None], [total - kept])
        rows[:, total - 1 - start, :b * k] = took[:, 0]
        pending = cache["pending"].copy()
        pending[table[0]] = block
        return logits[0], {**cache, "kv": kv, "pending": pending}

    def prefill(params, cache, table, tokens, n):
        kv = prefill_p(params, cache["kv"], table, tokens, n)
        return prompt_pass(cache, params, kv, table, tokens, 0, int(n))

    def chunk(params, cache, table, tokens, start, total):
        kv = chunk_p(params, cache["kv"], table, tokens, start, total)
        return prompt_pass(cache, params, kv, table, tokens, int(start),
                           int(total))

    def decode(params, cache, tables, lens, cur):
        tables, lens = np.asarray(tables), np.asarray(lens)
        w = len(lens)
        kv, pending = cache["kv"], cache["pending"].copy()
        rows = np.full((cfg.n_layers, w, width), -1, np.int32)
        kept = lens - lens % b
        blocks = pending[tables[:, 0]]
        blocks[np.arange(w), lens % b] = np.asarray(cur)
        full = (lens + 1) % b == 0
        if full.any():
            kv = commit_p(params, kv,
                          jnp.asarray(np.where(full[:, None], tables, 0)),
                          jnp.asarray(kept, jnp.int32), jnp.asarray(blocks))
            took = chosen(kv, w * b).reshape(cfg.n_layers, w, b * k)
            rows[:, full, b * k:] = took[:, full]
            blocks[full] = mask
            kept = kept + b * full
        logits, kv, took = denoise(params, kv, tables, kept, blocks,
                                   (lens + 1) % b)
        rows[:, :, :b * k] = took
        pending[tables[:, 0]] = blocks
        return logits, {"kv": kv, "pending": pending, "rows": rows}, \
            jnp.asarray(lens + 1, jnp.int32)

    return init_cache, prefill, chunk, decode


def routing_taken(cache):
    """int32 [L, rows, 2 * B * k] (module docstring)."""
    return cache["rows"]


def reference_kwargs(cfg, **override) -> dict:
    """What the reference takes from a configuration; ``override`` is the
    negative controls' hook (``qk_norm=False``, ``norm_topk=False``,
    ``block_mask=False``: a reference that leaves one rule out)."""
    return {"theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
            "top_k": cfg.top_k, "block": cfg.block_length,
            "mask": cfg.mask_token_id, "denoise": cfg.denoise_passes,
            "norm_topk": bool(cfg.norm_topk_prob), **override}


def num_params(cfg) -> int:
    from ray_tpu.models import sdar_moe
    return sdar_moe.num_params(cfg)
