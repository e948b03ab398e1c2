"""JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``: the DeepSeek-V3
block), as the harness knows it. The contract is the docstring of the
dense family's adapter beside this file; this family also routes
(``routing_taken``) and keeps a LATENT cache (one row of ``latent_dim``
numbers a token and layer, a pool of one array), which the harness never
sees: the cache is the program's pytree.

The block: multi-head latent attention in every layer (two low-rank
projections, a rotated part of 64 lanes beside an unrotated one of 128,
values of 128), a dense SwiGLU in the first ``first_k_dense_replace`` layers
and ``n_routed_experts`` routed experts beside ``n_shared_experts`` shared
ones after (sigmoid scores, selection under a bias that never weighs, top-k
weights normalised, times ``routed_scaling_factor``), an untied head. The
program is ray_tpu/models/joyai.py through the engine's paged programs
(whole prefill in the expanded form, chunk and decode absorbed, against
the latent pool); the plain reference benchmark/reference/joyai_f32.py has
the expanded form only.
"""

from __future__ import annotations

import functools

REFERENCE = "joyai_f32"
MODEL_SCOPES = ("embed", "norm", "q_proj", "kv_latent", "absorb", "attn",
                "mlp", "router", "experts", "shared_expert", "lm_head",
                "sample")


def sizes(config: dict, rehearsal: bool) -> dict:
    """Model sizes under the program's names, from the published keys (or
    the rehearsal's tiny preset). Every layer calls the paged kernel
    (``attn_layers`` = ``n_layers``); ``n_kv_heads`` 1: all heads read the
    one latent row; ``latent_dim`` / ``value_dim``: the row, and the lanes
    of it that are also the values (the latent readers' costs)."""
    if rehearsal:
        sz = dict(config["rehearsal"]["model"])
    else:
        if config["n_group"] != 1 or config["topk_group"] != 1 \
                or config["rope_scaling"] is not None \
                or config["moe_layer_freq"] != 1 \
                or config["scoring_func"] != "sigmoid":
            raise ValueError("the program has no group-limited routing, no "
                             "scaled rotation and routes every layer after "
                             "the dense ones by sigmoid scores")
        sz = {"vocab_size": config["vocab_size"],
              "dim": config["hidden_size"],
              "n_layers": config["num_hidden_layers"],
              "n_dense": config["first_k_dense_replace"],
              "n_heads": config["num_attention_heads"],
              "q_rank": config["q_lora_rank"],
              "kv_rank": config["kv_lora_rank"],
              "nope_dim": config["qk_nope_head_dim"],
              "rope_dim": config["qk_rope_head_dim"],
              "v_dim": config["v_head_dim"],
              "ffn_dim": config["intermediate_size"],
              "n_experts": config["n_routed_experts"],
              "top_k": config["num_experts_per_tok"],
              "expert_dim": config["moe_intermediate_size"],
              "n_shared": config["n_shared_experts"],
              "max_seq_len": config["engine"]["max_seq_len"],
              "rope_theta": float(config["rope_theta"]),
              "norm_eps": config["rms_norm_eps"],
              "scaling": float(config["routed_scaling_factor"]),
              "norm_topk_prob": config["norm_topk_prob"],
              "dtype": "bfloat16"}
        if sz["nope_dim"] + sz["rope_dim"] != config["qk_head_dim"]:
            raise ValueError("qk_head_dim is not nope + rope")
    sz["attn_layers"], sz["n_kv_heads"] = sz["n_layers"], 1
    sz["latent_dim"] = sz["kv_rank"] + sz["rope_dim"]
    sz["value_dim"] = sz["kv_rank"]
    return sz


def model_config(sz: dict, n_layers: int | None = None, trainer=None):
    """``n_layers=depth``: the first ``depth`` layers (check 1's model)."""
    import jax.numpy as jnp

    from ray_tpu.models import joyai
    keys = ("vocab_size", "dim", "n_dense", "n_heads", "q_rank", "kv_rank",
            "nope_dim", "rope_dim", "v_dim", "ffn_dim", "n_experts", "top_k",
            "expert_dim", "n_shared", "max_seq_len", "rope_theta", "norm_eps",
            "scaling", "norm_topk_prob")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[sz["dtype"]]
    return joyai.JoyaiConfig(
        n_layers=n_layers or sz["n_layers"], dtype=dtype,
        **{k: sz[k] for k in keys if k in sz})


def init_params(key, cfg):
    """The model module's initialiser itself: check 2's rebuild of the
    served weights is the engine's programs, so equal to the bit."""
    from ray_tpu.models import joyai
    return joyai.init_params(key, cfg)


def attention_backend(kind, cfg, page: int) -> str:
    from ray_tpu.serve.llm import kv_cache as kvc
    return kvc.resolve_attention_backend(kind, cfg, page)


@functools.lru_cache(maxsize=8)
def paged_programs(cfg, page: int, backend: str):
    """The engine's paged programs (kv_cache.py), jitted once per shape;
    the cache they keep holds the latent pool and the routing record."""
    import jax

    from ray_tpu.serve.llm import kv_cache as kvc
    return (
        lambda n_pages: kvc.init_paged_cache(cfg, n_pages, page),
        jax.jit(lambda p, kv, t, x, n: kvc.paged_prefill(
            p, kv, t, x, n, cfg, page)),
        jax.jit(lambda p, kv, t, x, s, n: kvc.paged_prefill_chunk(
            p, kv, t, x, s, n, cfg, page, backend)),
        jax.jit(lambda p, kv, t, sl, x: kvc.paged_decode_step(
            p, kv, t, sl, x, cfg, page, backend)))


def routing_taken(cache):
    """int32 [L_r, rows, k]: the experts the last call's rows chose."""
    return cache["routing"]


def reference_kwargs(cfg, **override) -> dict:
    """What the reference takes from a configuration; ``override`` is the
    negative controls' hook (benchmark/reference/joyai_f32.py lists them:
    a reference that leaves one rule out or gets it wrong)."""
    return {"theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
            "top_k": cfg.top_k, "scaling": float(cfg.scaling),
            "norm_topk": bool(cfg.norm_topk_prob), **override}


def num_params(cfg) -> int:
    from ray_tpu.models import joyai
    return joyai.num_params(cfg)
