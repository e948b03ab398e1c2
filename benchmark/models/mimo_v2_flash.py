"""MiMo-V2-Flash (``model_type`` ``mimo_v2_flash``), as the harness knows
it. The contract is the docstring of the dense family's adapter beside this
file; this family also routes (``routing_taken``), holds ONE CHIP'S SHARE of
the experts (``sizes()["n_experts"]`` is the experts held, which the readers
of the engine's expert counters divide by; ``router_experts`` the router's
published width) and has WINDOW LAYERS, whose rings the harness never sees:
the cache is the program's pytree, and the page table check 1 hands a
sequence gets its ring table put behind it here.

The block: GQA attention with query and key heads of ``head_dim`` lanes (the
first ``int(head_dim x partial_rotary_factor)`` rotated) beside value heads
of ``v_head_dim``, full layers (``hybrid_layer_pattern`` 0:
``num_key_value_heads`` KV heads, ``rope_theta``, the plain softmax) and
window layers (1: ``swa_num_key_value_heads``, ``swa_rope_theta``, the last
``sliding_window`` tokens, a learned sink logit a head in the softmax's
denominator), values times ``attention_value_scale``, a dense SwiGLU where
``moe_layer_freq`` is 0 and ``n_routed_experts`` routed experts where it is
1 (sigmoid scores, selection bias, top-k weights normalised, no shared
expert), an untied head. The program is ray_tpu/models/mimo.py through the
engine's paged programs; the plain reference benchmark/reference/
mimo_v2_flash_f32.py has a band in a mask where the program has a ring of
pages and one more column of the softmax where the kernel starts its
running maximum at the sink.

``sizes()`` states ``n_kv_heads`` (a full layer's), ``window_kv_heads``,
``head_dim`` (q and k) and ``value_dim``: the readers of benchmark/
costs_mixed.py take a layer kind's own.
"""

from __future__ import annotations

import functools

REFERENCE = "mimo_v2_flash_f32"
MODEL_SCOPES = ("embed", "norm", "attn", "mlp", "router", "experts",
                "lm_head", "sample")
# model configuration -> the engine's prefill chunk, by ``model_config``:
# ``paged_programs(cfg, page, backend)`` is not handed the engine section,
# and the rings it lays out must be the engine's (kv_cache.ring_pages)
_RING_SPAN: dict = {}


def sizes(config: dict, rehearsal: bool) -> dict:
    """Model sizes under the program's names, from the published keys (or
    the rehearsal's tiny preset). Every layer calls the paged kernel
    (``attn_layers`` = ``n_layers``), ``window_layers`` of them on a ring,
    ``n_dense`` of them with a dense feed-forward; ``n_experts``: the
    experts HELD."""
    if rehearsal:
        sz = dict(config["rehearsal"]["model"])
    else:
        n = config["num_hidden_layers"]
        if len(config["hybrid_layer_pattern"]) != n \
                or len(config["moe_layer_freq"]) != n \
                or config["scoring_func"] != "sigmoid" \
                or config["topk_method"] != "noaux_tc" \
                or not config["norm_topk_prob"] \
                or (config["n_group"], config["topk_group"]) != (1, 1) \
                or config["n_shared_experts"] is not None \
                or config["routed_scaling_factor"] is not None \
                or config["attention_bias"] \
                or not config["add_swa_attention_sink_bias"] \
                or config["add_full_attention_sink_bias"] \
                or config["sliding_window_size"] != config["sliding_window"] \
                or (config["swa_num_attention_heads"], config["swa_head_dim"],
                    config["swa_v_head_dim"]) != (
                    config["num_attention_heads"], config["head_dim"],
                    config["v_head_dim"]):
            raise ValueError(
                "the program has a layer kind a layer of hybrid_layer_"
                "pattern and moe_layer_freq, sigmoid scores under a "
                "selection bias with normalised weights, no shared expert, "
                "no scaling factor, no group-limited routing, no bias, a "
                "sink in the window layers alone and the same query heads "
                "and widths in both kinds of layer")
        sz = {"vocab_size": config["vocab_size"],
              "dim": config["hidden_size"],
              "n_layers": n,
              "n_heads": config["num_attention_heads"],
              "n_kv_heads": config["num_key_value_heads"],
              "window_kv_heads": config["swa_num_key_value_heads"],
              "head_dim": config["head_dim"],
              "value_dim": config["v_head_dim"],
              "rotary_dim": int(config["head_dim"]
                                * config["partial_rotary_factor"]),
              "ffn_dim": config["intermediate_size"],
              "router_experts": config["published"]["n_routed_experts"],
              "n_experts": config["n_routed_experts"],
              "top_k": config["num_experts_per_tok"],
              "expert_dim": config["moe_intermediate_size"],
              "window": config["sliding_window"],
              "pattern": list(config["hybrid_layer_pattern"]),
              "moe_freq": list(config["moe_layer_freq"]),
              "max_seq_len": config["engine"]["max_seq_len"],
              "rope_theta": float(config["rope_theta"]),
              "window_rope_theta": float(config["swa_rope_theta"]),
              "norm_eps": config["layernorm_epsilon"],
              "value_scale": float(config["attention_value_scale"]),
              "dtype": "bfloat16"}
    sz["prefill_chunk"] = (config["rehearsal"] if rehearsal
                           else config)["engine"]["prefill_chunk"]
    sz["attn_layers"] = sz["n_layers"]
    sz["window_layers"] = sum(map(bool, sz["pattern"][:sz["n_layers"]]))
    # the dense layers lead (``moe_layer_freq`` 0 1 1 ...): the accepted
    # reader of the grouped product takes routed layers = n_layers - n_dense
    sz["n_dense"] = sz["n_layers"] - sum(
        map(bool, sz["moe_freq"][:sz["n_layers"]]))
    return sz


def model_config(sz: dict, n_layers: int | None = None, trainer=None):
    """``n_layers=depth``: the first ``depth`` layers (check 1's model)."""
    import jax.numpy as jnp

    from ray_tpu.models import mimo
    keys = ("vocab_size", "dim", "n_heads", "n_kv_heads", "window_kv_heads",
            "head_dim", "value_dim", "rotary_dim", "ffn_dim", "top_k",
            "expert_dim", "window", "max_seq_len", "rope_theta",
            "window_rope_theta", "norm_eps", "value_scale")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[sz["dtype"]]
    cfg = mimo.MimoConfig(
        n_layers=n_layers or sz["n_layers"], dtype=dtype,
        n_experts=sz["router_experts"], experts_held=sz["n_experts"],
        pattern=tuple(sz["pattern"]), moe_freq=tuple(sz["moe_freq"]),
        **{k: sz[k] for k in keys if k in sz})
    _RING_SPAN[cfg] = sz["prefill_chunk"]
    return cfg


def init_params(key, cfg):
    """The model module's initialiser itself: check 2's rebuild of the
    served weights is the engine's programs, so equal to the bit."""
    from ray_tpu.models import mimo
    return mimo.init_params(key, cfg)


def attention_backend(kind, cfg, page: int) -> str:
    from ray_tpu.serve.llm import kv_cache as kvc
    return kvc.resolve_attention_backend(kind, cfg, page)


def with_rings(tables, full_w: int, ring: int):
    """The harness's page tables [..., full_w] with each sequence's RING
    table put behind its own: check 1 gives sequence s the pages ``1 + s x
    full_w ...`` of the growing pool, and it gets the entries ``1 + s x
    ring ...`` of the window pool here; a row of zeros (no sequence) a ring
    of zeros, the trash page."""
    import jax.numpy as jnp
    first = tables[..., :1]
    rings = 1 + (first - 1) // full_w * ring + jnp.arange(ring)
    return jnp.concatenate([tables, jnp.where(first > 0, rings, 0)], axis=-1)


def build_programs(cfg, page: int, backend: str, rings=with_rings):
    """The engine's paged programs (kv_cache.py), jitted once per shape;
    the cache they keep holds the four pools and the routing record, the
    window pools a ring a sequence of the growing pools'. ``rings``: how a
    ring table gets behind the harness's (a negative control hands a
    faulty one, tests/benchmark_suite/mimo_at_size.py)."""
    import jax

    from ray_tpu.serve.llm import kv_cache as kvc
    full_w = -(-cfg.max_seq_len // page)
    ring = kvc.ring_pages(cfg.window, page, _RING_SPAN[cfg])

    def tables(t):
        return rings(t, full_w, ring)

    return (
        lambda n_pages: kvc.init_paged_cache(
            cfg, n_pages, page,
            window_pages=(n_pages - 1) // full_w * ring + 1),
        jax.jit(lambda p, kv, t, x, n: kvc.paged_prefill(
            p, kv, tables(t), x, n, cfg, page)),
        jax.jit(lambda p, kv, t, x, s, n: kvc.paged_prefill_chunk(
            p, kv, tables(t), x, s, n, cfg, page, backend)),
        jax.jit(lambda p, kv, t, sl, x: kvc.paged_decode_step(
            p, kv, tables(t), sl, x, cfg, page, backend)))


paged_programs = functools.lru_cache(maxsize=8)(
    lambda cfg, page, backend: build_programs(cfg, page, backend))


def routing_taken(cache):
    """int32 [L_r, rows, k]: the experts the last call's rows chose, of
    ALL the router's (one held elsewhere adds nothing to the share)."""
    return cache["routing"]


def reference_kwargs(cfg, **override) -> dict:
    """What the reference takes from a configuration; ``override`` is the
    negative controls' hook (benchmark/reference/mimo_v2_flash_f32.py lists
    them: a reference that leaves one rule out or gets it wrong)."""
    return {"theta_full": float(cfg.rope_theta),
            "theta_window": float(cfg.window_rope_theta),
            "eps": float(cfg.norm_eps), "top_k": cfg.top_k,
            "window": cfg.window, "pattern": tuple(cfg.pattern),
            "rotary": cfg.rotary_dim, "value_scale": float(cfg.value_scale),
            "held": (0, cfg.experts_held), **override}


def num_params(cfg) -> int:
    from ray_tpu.models import mimo
    return mimo.num_params(cfg)
