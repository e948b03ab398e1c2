"""A program's tail does only what is used (ISSUE 56).

Two predicates a program reads in its own operands: the chunk program runs
its tail (the last row's final norm, the head over the vocabulary, the
sampler) only where the host says the chunk is a prompt's last (``final``,
a traced flag beside the row ``slot`` it writes), and ``kv_cache.sample_tokens`` draws only where a row of the
dispatch asks for a temperature. Neither may change a token, a pool or a
live row of the token vector. The expressions of the parent (commit
92086e9: the head on every chunk, the draw on every dispatch) are kept HERE
as the reference. ``tests/test_served_layout.py`` reads the same off the
programs compiled for a described v5e (the head's product and the random
bits stand only inside a ``conditional``), ``tests/test_profiling.py`` holds
the counters and the span arguments that say how often each engages.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import afmoe, joyai, lfm2_moe, llama, mimo, sdar_moe
from ray_tpu.serve.llm import LLMConfig, LLMEngine
from ray_tpu.serve.llm import kv_cache as kvc

ENGINE = dict(max_batch_size=4, page_size=8, num_pages=64, max_prompt_len=128,
              max_seq_len=192, prefill_chunk=16, decode_block=4,
              warmup_compile=False)
BLOCKS = {"dense": lambda: llama.llama_tiny(vocab_size=512),
          "lfm2": lfm2_moe.lfm2_moe_tiny, "joyai": joyai.joyai_tiny,
          "afmoe": afmoe.afmoe_tiny, "mimo": mimo.mimo_tiny}


def parent_sample_tokens(logits, rng, temperature, top_k: int = 0):
    """``kv_cache.sample_tokens`` as the parent held it: the draw whatever
    the temperatures, dropped under the ``where``."""
    greedy = jnp.argmax(logits, axis=-1)
    if top_k and top_k > 0:
        vals, idx = jax.lax.top_k(logits, top_k)
        scaled = vals / jnp.maximum(temperature[:, None], 1e-6)
        choice = jax.random.categorical(rng, scaled, axis=-1)
        sampled = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
    else:
        scaled = logits / jnp.maximum(temperature[:, None], 1e-6)
        sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy)


def _engine(block: str, backend: str = "gather", **over) -> LLMEngine:
    return LLMEngine(LLMConfig(model_config=BLOCKS[block](),
                               attention_kernel=backend,
                               **{**ENGINE, **over}))


# ---- (a) the chunk program ---------------------------------------------------

@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_a_chunk_that_arms_no_slot_leaves_what_the_parents_left(block,
                                                                backend):
    """A prompt of 40 tokens in chunks of 16, 16 and 8 through ``_chunk_fn``,
    the trash row on the first two, beside the parent's expression (the
    head and the sampler on every chunk): after every chunk the pools are
    equal to the last bit; the chunks that arm no slot leave every live
    row of the token vector as it was and hand back the placeholder; the
    last chunk's token is the parent's, in row ``slot``."""
    eng = _engine(block, backend)
    trash, slot, temp = eng.cfg.max_batch_size, 2, 0.7
    ring = eng._table_width - eng.max_pages_per_seq
    table = np.asarray(list(range(1, 1 + eng.max_pages_per_seq))
                       + list(range(1, 1 + ring)), np.int32)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (40,), 1, 500), np.int32)

    @jax.jit
    def parent(params, kv, toks_full, table, tokens, start, true_len, rng,
               temp, slot):
        logits, kv = kvc.paged_prefill_chunk(
            params, kv, table, tokens, start, true_len, eng.model_cfg,
            eng.cfg.page_size, eng._attn_backend, mesh=eng._mesh)
        tok = parent_sample_tokens(logits[None, :], rng, temp, eng.cfg.top_k)
        return tok[0], toks_full.at[slot].set(tok[0]), kv

    live = jnp.arange(100, 100 + trash + 1, dtype=jnp.int32)
    want_kv, want_toks = eng.kv, live
    got_kv, got_toks = jax.tree.map(jnp.copy, eng.kv), jnp.copy(live)
    for start, clen in ((0, 16), (16, 16), (32, 8)):
        final = start + clen >= len(prompt)
        toks = np.zeros((1, clen), np.int32)
        toks[0] = prompt[start:start + clen]
        operands = (table, toks, np.int32(start), np.int32(len(prompt)),
                    jax.random.PRNGKey(start), np.full((1,), temp, np.float32),
                    np.int32(slot if final else trash))
        want_tok, want_toks, want_kv = parent(
            eng.params, want_kv, want_toks, *operands)
        got_tok, got_toks, got_kv = eng._chunk_fn(clen)(
            eng.params, got_kv, got_toks, *operands, np.bool_(final))
        for (path, want), got in zip(
                jax.tree_util.tree_leaves_with_path(want_kv),
                jax.tree.leaves(got_kv)):
            assert bool(jnp.all(got == want)), (path, start)
        assert bool(jnp.all(got_toks[:trash] == want_toks[:trash]))
        if final:
            assert int(got_tok) == int(want_tok) == int(got_toks[slot])
        else:
            assert int(got_tok) == 0
            assert bool(jnp.all(got_toks[:trash] == live[:trash]))
    assert eng._chunk_fn(16)._cache_size() == 1       # one program a length


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_a_final_chunk_that_arms_no_slot_still_returns_its_token(block):
    """``slot`` is a write address and nothing else, as in ``_prefill_fn``
    (disagg's ``prefill_only`` passes the trash row and reads the token):
    a final chunk handed the trash row runs its head, returns the token
    the same chunk returns into a live row, and leaves every live row."""
    eng = _engine(block)
    trash = eng.cfg.max_batch_size
    ring = eng._table_width - eng.max_pages_per_seq
    table = np.asarray(list(range(1, 1 + eng.max_pages_per_seq))
                       + list(range(1, 1 + ring)), np.int32)
    toks = np.zeros((1, 16), np.int32)
    toks[0, :12] = np.arange(3, 15)
    live = jnp.arange(100, 100 + trash + 1, dtype=jnp.int32)

    def chunk(slot, final):
        tok, rows, _kv = eng._chunk_fn(16)(
            eng.params, jax.tree.map(jnp.copy, eng.kv), jnp.copy(live),
            table, toks, np.int32(0), np.int32(12), jax.random.PRNGKey(0),
            np.zeros((1,), np.float32), np.int32(slot), np.bool_(final))
        return int(tok), rows

    armed, rows = chunk(1, True)
    assert int(rows[1]) == armed != int(live[1])
    unarmed, rows = chunk(trash, True)
    assert unarmed == armed
    assert bool(jnp.all(rows[:trash] == live[:trash]))
    assert chunk(trash, False)[0] == 0


def test_with_a_block_length_above_one_the_chunk_computes_no_logits():
    """SDAR's chunk program leaves the slot's pending block and samples
    nothing: no equation of it, at any depth, writes the vocabulary."""
    eng = LLMEngine(LLMConfig(model_config=sdar_moe.sdar_moe_tiny(),
                              attention_kernel="gather", **ENGINE))
    vocab = eng.model_cfg.vocab_size
    traced = eng._chunk_fn(16).trace(
        eng.params, eng.kv, eng._dev_tokens,
        np.zeros((eng._table_width,), np.int32), np.zeros((1, 16), np.int32),
        np.int32(0), np.int32(5), eng._rng, np.zeros((1,), np.float32),
        np.int32(0), np.bool_(True))

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    found = list(eqns(traced.jaxpr.jaxpr))
    assert len(found) > 50
    assert not any(vocab in getattr(v.aval, "shape", ())
                   for eqn in found for v in eqn.outvars)


# ---- (b) the sampler ---------------------------------------------------------

TEMPS = {"greedy": [0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
         "sampled": [0.7, 1.0, 0.3, 1.5, 0.9, 2.0],
         "mixed": [0.0, 0.8, 0.0, 1.2, 0.0, 0.0]}


@pytest.mark.parametrize("top_k", [0, 5], ids=["whole", "top5"])
@pytest.mark.parametrize("temps", sorted(TEMPS))
def test_sample_tokens_gives_the_unconditional_expressions_tokens(temps,
                                                                  top_k):
    """Same key, same logits, same temperatures -> the parent's tokens, row
    for row: eager, jitted, and with the rows under two leading axes."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(1), (6, 512))
    temperature = jnp.asarray(TEMPS[temps], jnp.float32)
    for seed in range(4):
        key = jax.random.PRNGKey(seed)
        want = parent_sample_tokens(logits, key, temperature, top_k)
        for got in (
                kvc.sample_tokens(logits, key, temperature, top_k),
                jax.jit(kvc.sample_tokens, static_argnums=3)(
                    logits, key, temperature, top_k),
                # a block program's rows [W, B, V] under [W, B] temperatures
                # (the parent flattened them before its sampler)
                kvc.sample_tokens(logits.reshape(2, 3, -1), key,
                                  temperature.reshape(2, 3), top_k
                                  ).reshape(-1)):
            assert got.dtype == want.dtype
            assert np.array_equal(np.asarray(got), np.asarray(want))
    if temps == "greedy":
        assert np.array_equal(np.asarray(want),
                              np.asarray(jnp.argmax(logits, axis=-1)))


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("block", ["dense", "sdar"])
def test_a_stream_is_the_unconditional_samplers_stream(block, temperature,
                                                       monkeypatch):
    """A whole request (a chunked prompt, then decode blocks or, by
    diffusion, block programs) gives the tokens it gives with the parent's
    sampler in ``sample_tokens``' place: the engine's seed fixes the keys."""
    model = {**BLOCKS, "sdar": sdar_moe.sdar_moe_tiny}[block]
    prompt = [int(t) for t in np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (40,), 1, 500))]

    def stream():
        eng = LLMEngine(LLMConfig(model_config=model(),
                                  attention_kernel="gather", **ENGINE))
        eng.start()
        try:
            return eng.generate(prompt, max_tokens=12,
                                temperature=temperature)["tokens"]
        finally:
            eng.shutdown()

    got = stream()
    monkeypatch.setattr(
        kvc, "sample_tokens", lambda logits, rng, temperature, top_k=0:
        parent_sample_tokens(                       # rows flat, as the parent
            logits.reshape(-1, logits.shape[-1]), rng,
            temperature.reshape(-1), top_k).reshape(temperature.shape))
    assert got == stream() and len(got) == 12
