"""Falcon-H1 (``model_type`` ``falcon_h1``: tiiuae/Falcon-H1-34B-Instruct),
as the harness knows it. The contract is the docstring of benchmark/models/
llama.py. This family keeps slot state beside its pages (a convolution's
last columns and a float32 recurrent state of a matrix a head, a layer),
which the harness never sees: the cache is the program's pytree and check
1 drives two sequences by their page tables alone, a sequence's state row
being its first page (so the programs here ask for a state row a page:
``state_rows = n_pages``; the ENGINE holds slots + 1 rows and hands first
pages from a reserved range). It does not route: check 1 holds ONE limit,
``tolerance``.

The block: every layer a Mamba-2 state-space mixer and grouped-query
attention side by side off one norm, a dense SwiGLU, fixed multipliers on
every branch, head untied. The program is ray_tpu/models/falcon_h1.py
through the engine's paged programs; the plain reference
benchmark/reference/falcon_h1_f32.py.
"""

from __future__ import annotations

import functools

REFERENCE = "falcon_h1_f32"
MODEL_SCOPES = ("embed", "norm", "attn", "ssm", "ssm_in", "ssm_conv",
                "ssm_scan", "ssm_update", "ssm_out", "mlp", "lm_head",
                "sample")

_MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")


def _block():
    """The program's module; a checkout whose ray_tpu has no such block
    (the parent of the PR that brought it) says so and exits."""
    try:
        from ray_tpu.models import falcon_h1
    except ImportError as e:
        raise SystemExit(
            f"benchmark/models/falcon_h1.py: this checkout cannot run the "
            f"configuration: its ray_tpu has no models/falcon_h1.py, the "
            f"block with a state-space mixer beside attention ({e})")
    return falcon_h1


def sizes(config: dict, rehearsal: bool) -> dict:
    """Model sizes under the program's names, from the published keys (or
    the rehearsal's tiny preset)."""
    if rehearsal:
        sz = dict(config["rehearsal"]["model"])
    else:
        for key, want in (("mamba_norm_before_gate", False),
                          ("mamba_rms_norm", True),
                          ("mamba_conv_bias", True),
                          ("tie_word_embeddings", False)):
            if config[key] is not want:
                raise ValueError(f"{key}={config[key]!r}: the block is "
                                 f"written for {want!r}")
        if config["mamba_d_ssm"] != config["mamba_n_heads"] \
                * config["mamba_d_head"]:
            raise ValueError("mamba_d_ssm is not mamba_n_heads x "
                             "mamba_d_head")
        sz = {"vocab_size": config["vocab_size"],
              "dim": config["hidden_size"],
              "n_layers": config["num_hidden_layers"],
              "n_heads": config["num_attention_heads"],
              "n_kv_heads": config["num_key_value_heads"],
              "head_dim": config["head_dim"],
              "ffn_dim": config["intermediate_size"],
              "ssm_heads": config["mamba_n_heads"],
              "ssm_head_dim": config["mamba_d_head"],
              "ssm_state": config["mamba_d_state"],
              "ssm_groups": config["mamba_n_groups"],
              "ssm_conv": config["mamba_d_conv"],
              "ssm_chunk": config["mamba_chunk_size"],
              "max_seq_len": config["engine"]["max_seq_len"],
              "rope_theta": float(config["rope_theta"]),
              "norm_eps": config["rms_norm_eps"],
              "ssm_multipliers": list(config["ssm_multipliers"]),
              "mlp_multipliers": list(config["mlp_multipliers"]),
              **{k: float(config[k]) for k in _MULTIPLIERS},
              "dtype": "bfloat16"}
    sz["attn_layers"] = sz["n_layers"]
    return sz


def model_config(sz: dict, n_layers: int | None = None, trainer=None):
    """``n_layers=depth``: the first ``depth`` layers (check 1's model;
    every layer is alike)."""
    import jax.numpy as jnp

    falcon_h1 = _block()
    keys = ("vocab_size", "dim", "n_heads", "n_kv_heads", "head_dim",
            "ffn_dim", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups",
            "ssm_conv", "ssm_chunk", "max_seq_len", "rope_theta", "norm_eps",
            *_MULTIPLIERS)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[sz["dtype"]]
    more = {k: tuple(sz[k]) for k in ("ssm_multipliers", "mlp_multipliers")
            if k in sz}
    return falcon_h1.FalconH1Config(
        n_layers=n_layers or sz["n_layers"], dtype=dtype, **more,
        **{k: sz[k] for k in keys if k in sz})


def init_params(key, cfg):
    """The model module's initialiser itself: check 2's rebuild of the
    served weights is the engine's programs, so equal to the bit."""
    return _block().init_params(key, cfg)


def attention_backend(kind, cfg, page: int) -> str:
    from ray_tpu.serve.llm import kv_cache as kvc
    return kvc.resolve_attention_backend(kind, cfg, page)


@functools.lru_cache(maxsize=8)
def paged_programs(cfg, page: int, backend: str):
    """The engine's paged programs (kv_cache.py), jitted once per shape;
    the cache they keep holds pages and, a layer, the two state arrays at
    a row a page (the harness names a sequence by its pages alone)."""
    import jax

    from ray_tpu.serve.llm import kv_cache as kvc
    return (
        lambda n_pages: kvc.init_paged_cache(cfg, n_pages, page,
                                             state_rows=n_pages),
        jax.jit(lambda p, kv, t, x, n: kvc.paged_prefill(
            p, kv, t, x, n, cfg, page)),
        jax.jit(lambda p, kv, t, x, s, n: kvc.paged_prefill_chunk(
            p, kv, t, x, s, n, cfg, page, backend)),
        jax.jit(lambda p, kv, t, sl, x: kvc.paged_decode_step(
            p, kv, t, sl, x, cfg, page, backend)))


def reference_kwargs(cfg, **override) -> dict:
    """What the reference takes from a configuration; ``override`` is the
    negative controls' hook (benchmark/reference/falcon_h1_f32.py lists
    them: a reference that leaves one rule out or gets it wrong)."""
    return {"theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
            "groups": cfg.ssm_groups, "state": cfg.ssm_state,
            "head_p": cfg.ssm_head_dim,
            "ssm_multipliers": tuple(cfg.ssm_multipliers),
            "mlp_multipliers": tuple(cfg.mlp_multipliers),
            **{k: float(getattr(cfg, k)) for k in _MULTIPLIERS}, **override}


def num_params(cfg) -> int:
    return _block().num_params(cfg)
