"""The dense Llama / Mistral block, as the harness knows it: everything
that names this family sits here, and the rest of the harness reaches the
family through ``common.load_module("models", config["model_family"])``.

What a family's adapter provides (a second family is a second file with
these names; the harness never edits this one):

    REFERENCE      name of its plain reference under benchmark/reference/
    MODEL_SCOPES   the ``jax.named_scope`` names of the model's own
                   arithmetic (``model_op_share`` counts these)
    sizes(config, rehearsal)          published keys -> the program's names;
                                      always holds n_layers, n_heads,
                                      n_kv_heads, dim (the readers use
                                      them) and vocab_size (the traffic)
    model_config(sz, n_layers=None, trainer=None)
                                      the program's configuration object
    init_params(key, cfg)             the program's own initialiser
    reference_kwargs(cfg, **override) what the reference takes from a config
    logical_axes(cfg), loss_fn(params, batch, cfg, mesh), num_params(cfg)
                                      the train path
    params(sz), train_flops_per_token(sz, seq_len)
                                      the family's counts (``train_mfu``)
    attention_backend(kind, cfg, page)
                                      the backend the engine's
                                      ``attention_kernel`` resolves to for
                                      this block ("pallas" | "gather")
    paged_programs(cfg, page, backend)
        -> (init_cache(n_pages) -> cache,
            prefill(params, cache, table [P], tokens [1, W], n)
                -> (logits [V], cache),
            chunk(params, cache, table [P], tokens [1, W], start, total)
                -> (logits [V], cache),
            decode(params, cache, tables [B, P], lens [B], tokens [B])
                -> (logits [B, V], cache, lens + 1))
                                      the programs check 1 drives, jitted,
                                      the same objects on every call with
                                      the same arguments; the cache is an
                                      opaque pytree (pages, and whatever
                                      state a block keeps beside them)

``sizes()`` may also hold ``attn_layers``: how many of ``n_layers`` call the
paged attention kernel (absent = all of them; the trace readers count a
decode execution's steps as kernel calls over it). ``model_config(sz,
n_layers=depth)`` is check 1's model: where layers differ in kind, the
first ``depth`` of them have to hold every kind (the configuration's
``checks.logits.depth`` is chosen so).

A family with routed experts also provides (and only such a family: it is
by this name that check 1 knows one, and without it none of check 1's
routing code runs):

    routing_taken(cache) -> int32 [L_r, rows, k]
                                      the experts the LAST program call
                                      chose for each of its token rows (a
                                      prefill's or a chunk's W rows, a
                                      decode's B rows; more rows than the
                                      call had are ignored), L_r the layers
                                      that route, in order; where the
                                      program keeps the record is its matter

Its reference takes ``routing=`` and provides ``routing_slack``
(benchmark/reference/__init__.py), and its configuration's
``checks.logits`` states ``rms_tolerance``, ``routing_slack`` and
``routing_flip_share_max`` beside ``tolerance``. ``checks.logits.backend`` (absent = "pallas") is the
attention backend a chip run must resolve to; a file that states "gather"
says why in ``backend_why``.

Which block the engine's paged programs run is the program's matter: the
harness hands them the ``cfg`` built here.
"""

from __future__ import annotations

import functools

REFERENCE = "llama_f32"
MODEL_SCOPES = ("embed", "norm", "attn", "mlp", "lm_head", "sample", "loss",
                "optimizer")
# keys of a configuration's ``trainer`` section that are this block's own
TRAINER_KEYS = ("remat_policy", "ce_chunk", "ce_remat", "attn_impl")


def sizes(config: dict, rehearsal: bool) -> dict:
    """Model sizes under the program's names, from the published keys (or
    the rehearsal's tiny preset)."""
    if rehearsal:
        return dict(config["rehearsal"]["model"])
    return {"vocab_size": config["vocab_size"], "dim": config["hidden_size"],
            "n_layers": config["num_hidden_layers"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "ffn_dim": config["intermediate_size"],
            "max_seq_len": (config.get("engine") or {}).get(
                "max_seq_len") or config["trainer"]["seq_len"],
            "rope_theta": config["rope_theta"],
            "norm_eps": config["rms_norm_eps"],
            "dtype": config["torch_dtype"]}


def model_config(sz: dict, n_layers: int | None = None,
                 trainer: dict | None = None):
    import jax.numpy as jnp

    from ray_tpu.models import llama
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[sz["dtype"]]
    recipe = {k: trainer[k] for k in TRAINER_KEYS} if trainer else {}
    return llama.LlamaConfig(
        vocab_size=sz["vocab_size"], dim=sz["dim"],
        n_layers=n_layers or sz["n_layers"], n_heads=sz["n_heads"],
        n_kv_heads=sz["n_kv_heads"], ffn_dim=sz["ffn_dim"],
        max_seq_len=sz["max_seq_len"], rope_theta=sz["rope_theta"],
        norm_eps=sz.get("norm_eps", 1e-5), dtype=dtype, **recipe)


def init_params(key, cfg):
    from ray_tpu.models import llama
    return llama.init_params(key, cfg)


def attention_backend(kind, cfg, page: int) -> str:
    from ray_tpu.serve.llm import kv_cache as kvc
    return kvc.resolve_attention_backend(kind, cfg, page)


@functools.lru_cache(maxsize=8)
def paged_programs(cfg, page: int, backend: str):
    """The engine's paged programs (kv_cache.py), jitted once per shape."""
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


def reference_kwargs(cfg, **override) -> dict:
    """What the reference takes from a configuration. ``override`` is the
    negative controls' hook (``use_rope=False``: a reference that leaves
    the rotary embedding out)."""
    return {"theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
            **override}


def logical_axes(cfg):
    from ray_tpu.models import llama
    return llama.logical_axes(cfg)


def loss_fn(params, batch, cfg, mesh):
    from ray_tpu.models import llama
    return llama.loss_fn(params, batch, cfg, mesh)


def num_params(cfg) -> int:
    from ray_tpu.models import llama
    return llama.num_params(cfg)


def params(sz: dict) -> int:
    """Parameters of the dense block stack, embedding and output head."""
    hd = sz["dim"] // sz["n_heads"]
    per_layer = (sz["dim"] * (sz["n_heads"] + 2 * sz["n_kv_heads"]) * hd
                 + sz["n_heads"] * hd * sz["dim"]
                 + 3 * sz["dim"] * sz["ffn_dim"] + 2 * sz["dim"])
    return 2 * sz["vocab_size"] * sz["dim"] + sz["dim"] \
        + sz["n_layers"] * per_layer


def train_flops_per_token(sz: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one token needs: 6 per parameter that
    multiplies it (the embedding table is a lookup, so it is left out)
    plus causal attention, 6 * layers * seq_len * dim (QK^T and PV, half
    the square, forward 2x + backward 4x). Recomputation is not counted."""
    matmul_params = params(sz) - sz["vocab_size"] * sz["dim"]
    return 6.0 * matmul_params + 6.0 * sz["n_layers"] * seq_len * sz["dim"]
