"""The engine's programs (the dense block, LFM2-MoE, since ISSUE 42
SDAR-MoE's block program and since ISSUE 48 the latent block, JoyAI, whose
entries the PARENT's tree wrote before that PR touched the kernels' file)
lower to the text they lowered to when
``tests/data/engine_program_hashes.json`` was written: decode tiers,
speculative verify, prefill and chunk buckets, on the gather and the pallas
(interpreted) backends. A PR that works on another block's path (generation
by diffusion over blocks: ISSUE 38) shares ``kv_cache._span_step``, the
dispatch and the harvest with these; "nothing moves in their cells" is held
here, on the CPU, before any chip is asked. A PR that changes WHICH program
the loop dispatches (ISSUE 42: the idle tier's k) changes no program: the
SDAR entries were written by the parent commit's engine, and every k the
loop can pick is one ``start()`` warmed.

A PR that MEANS to change one of these programs rewrites the file and says
so: ``python tests/test_engine_program_hashes.py`` (from the repo's root).
The text depends on the jax that lowers it, so the file records the version
and another one skips the comparison. Not a device number.
"""

import functools
import hashlib
import json
import os
import sys

import jax
import numpy as np
import pytest

if __name__ == "__main__":      # run as a script: the repo's root on the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from ray_tpu.models import joyai, lfm2_moe, llama, sdar_moe  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "engine_program_hashes.json")
ENGINE = dict(max_batch_size=4, page_size=8, num_pages=64, max_prompt_len=128,
              max_seq_len=192, prefill_chunk=32, decode_block=8,
              pressure_decode_block=4, pipeline_depth=2, spec_draft_len=3,
              warmup_compile=False)
BLOCKS = {"dense": lambda: llama.llama_tiny(vocab_size=512),
          "lfm2": lfm2_moe.lfm2_moe_tiny,
          "sdar": sdar_moe.sdar_moe_tiny,
          "joyai": joyai.joyai_tiny}
BACKENDS = ("gather", "pallas")
PROGRAMS = ("decode_1", "decode_4", "decode_8", "verify", "prefill_32",
            "chunk_16")
# a block length of 4: the tiers of 1, 4 and 8 tokens are one and two
# whole blocks (decode_<blocks>); no speculation beside a pending block
SDAR_PROGRAMS = ("decode_1", "decode_2", "prefill_32", "chunk_16")
CASES = [(blk, backend, prog) for blk in BLOCKS for backend in BACKENDS
         for prog in (SDAR_PROGRAMS if blk == "sdar" else PROGRAMS)
         if not (blk == "lfm2" and prog == "verify")]     # slot state: none


def _lowered(eng: LLMEngine, program: str) -> str:
    """The program's lowered text, its operands built as the loop builds
    them (numpy, or the engine's device state)."""
    kind, _, n = program.partition("_")
    w = eng.cfg.max_batch_size
    idx = eng._slot_index((), w)
    state = (eng.params, eng.kv, eng._pt_dev, eng._sl_dev, eng._dev_tokens,
             eng._rng, eng._temps_dev, idx)
    if kind == "decode":
        return eng._decode.lower(*state, int(n)).as_text()
    if kind == "verify":
        return eng._verify.lower(*state, np.full(
            (w, eng.cfg.spec_draft_len), -1, np.int32)).as_text()
    table = np.zeros((eng.max_pages_per_seq,), np.int32)
    toks = np.zeros((1, int(n)), np.int32)
    tail = (eng._rng, np.zeros((1,), np.float32), np.int32(0))
    if kind == "prefill":
        return eng._prefill_fn(int(n)).lower(
            eng.params, eng.kv, eng._dev_tokens, table, toks, np.int32(5),
            *tail).as_text()
    return eng._chunk_fn(int(n)).lower(
        eng.params, eng.kv, eng._dev_tokens, table, toks, np.int32(0),
        np.int32(5), *tail).as_text()


@functools.cache
def _hashes(block: str, backend: str) -> dict:
    """One engine a (block, backend), built once a process."""
    eng = LLMEngine(LLMConfig(model_config=BLOCKS[block](),
                              attention_kernel=backend, **ENGINE))
    return {prog: hashlib.sha256(_lowered(eng, prog).encode()).hexdigest()
            for b, k, prog in CASES if (b, k) == (block, backend)}


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        data = json.load(f)
    if data["jax"] != jax.__version__:
        pytest.skip(f"recorded under jax {data['jax']}, this is "
                    f"{jax.__version__}: rewrite {DATA}")
    return data["programs"]


@pytest.mark.parametrize("block,backend,program", CASES,
                         ids=["-".join(c) for c in CASES])
def test_program_lowers_to_the_recorded_text(recorded, block, backend,
                                             program):
    assert _hashes(block, backend)[program] \
        == recorded[f"{block}-{backend}-{program}"], (
        "the program's lowered text changed: if that was meant, rewrite "
        "tests/data/engine_program_hashes.json (this file, run as a script)")


TIER_CASES = [("dense", {}), ("dense", {"spec_decode_enabled": True}),
              ("lfm2", {}), ("sdar", {})]


@pytest.mark.parametrize("block,over", TIER_CASES,
                         ids=["dense", "dense-spec", "lfm2", "sdar"])
def test_every_k_the_loop_can_pick_is_one_start_warmed(block, over):
    """``_select_block`` in every state of the queue and at every tier of
    the idle lead (ISSUE 42) against the k of the decode programs that
    ``_warmup_decode_programs`` runs: no new program, so no compile under
    traffic. Without speculation (which caps the idle tier) the loop can
    reach every warmed tier too."""
    from ray_tpu.serve.llm import lead as lead_mod

    eng = LLMEngine(LLMConfig(model_config=BLOCKS[block](),
                              attention_kernel="gather",
                              **{**ENGINE, **over}))
    warmed = set()

    def decode(*operands):          # k is the program's last operand
        warmed.add(operands[-1])
        out = (None, eng._dev_tokens, eng.kv, eng._sl_dev, eng._rng)
        return out + ((None,) if eng._cache_spec.routed_layers else ())

    eng._decode = decode
    eng._verify = lambda *operands: decode(*operands, None)[:5]
    eng._warmup_decode_programs()
    warmed.discard(None)
    picked = set()
    for _tier in eng._lead.tiers:
        for waiting, free, prefilling in (([], [0], []), ([object()], [0], []),
                                          ([object()], [], []),
                                          ([], [0], [object()])):
            eng._waiting, eng.free_slots = waiting, free
            eng._prefilling = prefilling
            picked.add(eng._select_block())
        for _ in range(lead_mod.CLIMB_DRY):
            eng._lead.observe(1, eng._collector.pause_n)
    assert eng._lead.k == eng._blocks_of(ENGINE["decode_block"])
    assert picked <= warmed, (picked, warmed)
    if not over:
        assert picked == warmed


if __name__ == "__main__":
    out = {"jax": jax.__version__, "programs": {}}
    for blk in BLOCKS:
        for kernel in BACKENDS:
            for name, digest in _hashes(blk, kernel).items():
                out["programs"][f"{blk}-{kernel}-{name}"] = digest
    os.makedirs(os.path.dirname(DATA), exist_ok=True)
    with open(DATA, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"{len(out['programs'])} programs -> {DATA}")
