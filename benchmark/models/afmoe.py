"""Trinity-Large (``model_type`` ``afmoe``), as the harness knows it. The
contract is the docstring of the dense family's adapter beside this file;
this family also routes (``routing_taken``), holds ONE CHIP'S SHARE of the
experts (``sizes()["n_experts"]`` is the experts held, which the readers of
the engine's expert counters divide by; ``router_experts`` the router's
published width) and has WINDOW LAYERS, whose rings the harness never sees:
the cache is the program's pytree, and the page table check 1 hands a
sequence gets its ring table put behind it here.

The block: gated GQA attention under four norms a layer, three layers of
four seeing a window of ``sliding_window`` tokens (rotated) and the fourth
every token (not rotated), a dense SwiGLU in the first ``num_dense_layers``
layers and ``num_experts`` routed experts beside one shared after (sigmoid
scores, selection bias, top-k weights normalised, times ``route_scale``),
an untied head. The program is ray_tpu/models/afmoe.py through the engine's
paged programs; the plain reference benchmark/reference/afmoe_f32.py has a
band in a mask where the program has a ring of pages.
"""

from __future__ import annotations

import functools

REFERENCE = "afmoe_f32"
MODEL_SCOPES = ("embed", "norm", "attn", "gate", "mlp", "router", "experts",
                "shared_expert", "lm_head", "sample")
# model configuration -> the engine's prefill chunk, by ``model_config``:
# ``paged_programs(cfg, page, backend)`` is not handed the engine section,
# and the rings it lays out must be the engine's (kv_cache.ring_pages)
_RING_SPAN: dict = {}


def sizes(config: dict, rehearsal: bool) -> dict:
    """Model sizes under the program's names, from the published keys (or
    the rehearsal's tiny preset). Every layer calls the paged kernel
    (``attn_layers`` = ``n_layers``), ``window_layers`` of them on a ring;
    ``n_experts``: the experts HELD."""
    if rehearsal:
        sz = dict(config["rehearsal"]["model"])
    else:
        kinds = {"sliding_attention": True, "full_attention": False}
        every = config["global_attn_every_n_layers"]
        types = config["layer_types"]
        if [kinds[t] for t in types] != [(i + 1) % every != 0
                                         for i in range(len(types))] \
                or len(types) != config["num_hidden_layers"] \
                or config["score_func"] != "sigmoid" \
                or not config["route_norm"] \
                or config["rope_scaling"] is not None \
                or (config["n_group"], config["topk_group"]) != (1, 1) \
                or config["num_shared_experts"] != 1:
            raise ValueError(
                "the program has a full layer every global_attn_every_n_"
                "layers-th and window layers between, sigmoid scores with "
                "normalised weights, one shared expert, no group-limited "
                "routing and no scaled rotation")
        sz = {"vocab_size": config["vocab_size"],
              "dim": config["hidden_size"],
              "n_layers": config["num_hidden_layers"],
              "n_dense": config["num_dense_layers"],
              "n_heads": config["num_attention_heads"],
              "n_kv_heads": config["num_key_value_heads"],
              "head_dim": config["head_dim"],
              "ffn_dim": config["intermediate_size"],
              "router_experts": config["published"]["num_experts"],
              "n_experts": config["num_experts"],
              "top_k": config["num_experts_per_tok"],
              "expert_dim": config["moe_intermediate_size"],
              "window": config["sliding_window"],
              "global_every": every,
              "max_seq_len": config["engine"]["max_seq_len"],
              "rope_theta": float(config["rope_theta"]),
              "norm_eps": config["rms_norm_eps"],
              "scaling": float(config["route_scale"]),
              "mup": bool(config["mup_enabled"]),
              "dtype": "bfloat16"}
    sz["prefill_chunk"] = (config["rehearsal"] if rehearsal
                           else config)["engine"]["prefill_chunk"]
    sz["attn_layers"] = sz["n_layers"]
    sz["window_layers"] = sum(
        (i + 1) % sz["global_every"] != 0 for i in range(sz["n_layers"]))
    return sz


def model_config(sz: dict, n_layers: int | None = None, trainer=None):
    """``n_layers=depth``: the first ``depth`` layers (check 1's model)."""
    import jax.numpy as jnp

    from ray_tpu.models import afmoe
    keys = ("vocab_size", "dim", "n_dense", "n_heads", "n_kv_heads",
            "head_dim", "ffn_dim", "top_k", "expert_dim", "window",
            "global_every", "max_seq_len", "rope_theta", "norm_eps",
            "scaling", "mup")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[sz["dtype"]]
    cfg = afmoe.AfmoeConfig(
        n_layers=n_layers or sz["n_layers"], dtype=dtype,
        n_experts=sz["router_experts"], experts_held=sz["n_experts"],
        **{k: sz[k] for k in keys if k in sz})
    _RING_SPAN[cfg] = sz["prefill_chunk"]
    return cfg


def init_params(key, cfg):
    """The model module's initialiser itself: check 2's rebuild of the
    served weights is the engine's programs, so equal to the bit."""
    from ray_tpu.models import afmoe
    return afmoe.init_params(key, cfg)


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
    the cache they keep holds both pools and the routing record, the
    window pool a ring a sequence of the growing pool's. ``rings``: how a
    ring table gets behind the harness's (a negative control hands a
    faulty one, tests/benchmark_suite/trinity_at_size.py)."""
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
    negative controls' hook (benchmark/reference/afmoe_f32.py lists them:
    a reference that leaves one rule out or gets it wrong)."""
    return {"theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
            "top_k": cfg.top_k, "scaling": float(cfg.scaling),
            "window": cfg.window, "global_every": cfg.global_every,
            "held": (0, cfg.experts_held), "mup": bool(cfg.mup), **override}


def num_params(cfg) -> int:
    from ray_tpu.models import afmoe
    return afmoe.num_params(cfg)
