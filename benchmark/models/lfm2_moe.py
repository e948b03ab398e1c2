"""LFM2-MoE (``model_type`` ``lfm2_moe``: LiquidAI/LFM2-8B-A1B), as the
harness knows it. The contract is the docstring of benchmark/models/
llama.py; this family also routes (``routing_taken``) and keeps slot state
beside its pages (the conv mixers' last columns), which the harness never
sees: the cache is the program's pytree and check 1 drives two sequences
by their page tables alone.

The block: layer kinds from ``layer_types`` (gated short convolution or
grouped-query attention with a per-head q/k norm), a dense SwiGLU in the
first ``num_dense_layers`` layers and ``num_experts`` routed experts after
(sigmoid scores, selection under a bias that never weighs, top-k weights
normalised), embedding and head tied. The program is
ray_tpu/models/lfm2_moe.py through the engine's paged programs; the plain
reference benchmark/reference/lfm2_moe_f32.py.
"""

from __future__ import annotations

import functools

REFERENCE = "lfm2_moe_f32"
MODEL_SCOPES = ("embed", "norm", "attn", "conv", "mlp", "router", "experts",
                "lm_head", "sample")


def sizes(config: dict, rehearsal: bool) -> dict:
    """Model sizes under the program's names, from the published keys (or
    the rehearsal's tiny preset). ``attn_layers``: the layers that call the
    paged attention kernel (the trace readers divide kernel calls by it)."""
    if rehearsal:
        sz = dict(config["rehearsal"]["model"])
    else:
        sz = {"vocab_size": config["vocab_size"],
              "dim": config["hidden_size"],
              "layer_types": list(config["layer_types"]),
              "n_dense": config["num_dense_layers"],
              "n_heads": config["num_attention_heads"],
              "n_kv_heads": config["num_key_value_heads"],
              "head_dim": config["hidden_size"]
              // config["num_attention_heads"],
              "ffn_dim": config["intermediate_size"],
              "n_experts": config["num_experts"],
              "top_k": config["num_experts_per_tok"],
              "expert_dim": config["moe_intermediate_size"],
              "conv_kernel": config["conv_L_cache"],
              "max_seq_len": config["engine"]["max_seq_len"],
              "rope_theta": float(config["rope_theta"]),
              "norm_eps": config["norm_eps"],
              "scaling": float(config["routed_scaling_factor"]),
              "norm_topk_prob": config["norm_topk_prob"],
              "use_expert_bias": config["use_expert_bias"],
              "dtype": "bfloat16"}
        if len(sz["layer_types"]) != config["num_hidden_layers"]:
            raise ValueError("layer_types does not list num_hidden_layers "
                             "layers")
    sz["n_layers"] = len(sz["layer_types"])
    sz["attn_layers"] = sum(k == "full_attention" for k in sz["layer_types"])
    return sz


def model_config(sz: dict, n_layers: int | None = None, trainer=None):
    """``n_layers=depth``: the first ``depth`` layers (check 1's model)."""
    import jax.numpy as jnp

    from ray_tpu.models import lfm2_moe
    kinds = tuple(sz["layer_types"][:n_layers or sz["n_layers"]])
    keys = ("vocab_size", "dim", "n_dense", "n_heads", "n_kv_heads",
            "head_dim", "ffn_dim", "n_experts", "top_k", "expert_dim",
            "conv_kernel", "max_seq_len", "rope_theta", "norm_eps", "scaling",
            "norm_topk_prob", "use_expert_bias")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[sz["dtype"]]
    return lfm2_moe.Lfm2MoeConfig(
        layer_types=kinds, dtype=dtype, **{k: sz[k] for k in keys if k in sz})


def init_params(key, cfg):
    """The model module's jitted initialiser itself: check 2's rebuild of
    the served weights is the engine's program, so equal to the bit."""
    from ray_tpu.models import lfm2_moe
    return lfm2_moe.init_params(key, cfg)


def attention_backend(kind, cfg, page: int) -> str:
    from ray_tpu.serve.llm import kv_cache as kvc
    return kvc.resolve_attention_backend(kind, cfg, page)


@functools.lru_cache(maxsize=8)
def paged_programs(cfg, page: int, backend: str):
    """The engine's paged programs (kv_cache.py), jitted once per shape;
    the cache they keep holds pages, slot state and the routing record."""
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
    negative controls' hook (``qk_norm=False``, ``norm_topk=False``,
    ``bias_weighs=True``: a reference that leaves one rule out)."""
    return {"theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
            "top_k": cfg.top_k, "scaling": float(cfg.scaling),
            "norm_topk": bool(cfg.norm_topk_prob),
            "use_bias": bool(cfg.use_expert_bias), **override}


def num_params(cfg) -> int:
    from ray_tpu.models import lfm2_moe
    return lfm2_moe.num_params(cfg)
