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
loop can pick is one ``start()`` warmed. ISSUE 50 (ISSUE 49 asked again)
changed the grouped expert product: the ROUTED families' entries (lfm2,
sdar, joyai: 30) were rewritten by that PR's tree; the dense block's
twelve (decode tiers, verify, prefill, chunk, both backends) are commit
c3e050f's, letter for letter, and a pin (``PARENT_DENSE``) held them a
second time so that a rewrite of the file cannot move them unseen: no
Mistral cell runs a changed program. ISSUE 52 ADDS the block with window
layers (``afmoe``: ten programs, no verify) and leaves the 42 others as
they were recorded: that PR's edits of kv_cache.py and of the walking
kernel lower every one of them to the parent's text. ISSUE 53 records
again the SIX programs that call the walking body on pools of K and V
(SDAR's two block programs, Trinity's three decode tiers and its chunk, on
the pallas backend: their kernel writes the call's rows and nothing is
scattered before it); a pin (``PARENT_53``) held the other 46 to the parent's.
ISSUE 54 records again the THIRTY programs of the dense block, afmoe and
SDAR (their q / k / v / gate projections are held head-major and read by
``"btd,hdk->bthk"``: the text's parameter shapes and that einsum's name among
the scopes move, no equation is added or taken away) and leaves the
twenty-two of LFM2 and JoyAI to the parent's text and scopes. ISSUE 55 ADDS
the block whose key and value rows differ in width and whose layer kinds
differ in KV heads and in a sink (``mimo``: ten programs, no verify) and
leaves the 52 others as they were recorded (then ``PARENT_54``): that PR's edits
of kv_cache.py and of the walking kernel (a value width, ``sink=``) lower
every one of them to the parent's text. ISSUE 56 records again EVERY program
that ends in a head or a sampler, which is all but four: ``sample_tokens``
draws under a ``cond`` on "a row of the dispatch samples" (the decode tiers,
verify, the whole prefill and the chunk of every block; SDAR's block
programs, whose own ``cond`` around the sampler folded into it), and the
chunk program's tail (final norm, row pick, head, sampler) stands under a
``cond`` on ``final``, one more operand beside ``slot`` (every ``chunk_16``
but SDAR's: text and scopes). SDAR's ``prefill_32`` and ``chunk_16`` compute
neither logits nor a sample and lower to the parent's text (an operand that
nothing reads is not in it); the chunk's SCOPES moved all the same (the
head's equations, which the parent traced and the lowering dropped, are no
longer traced: ``kv_cache.paged_chunk_walk``). ``PINNED`` below holds what
that PR's tree lowered, a (block, backend), so that a later rewrite of the
file cannot move a program unseen; it took the place of three pins
(``PARENT_DENSE``, ``PARENT_53``, ``PARENT_55``) that had come to hold the
same tree.

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

from ray_tpu.models import (afmoe, joyai, lfm2_moe, llama, mimo,  # noqa: E402
                            sdar_moe)
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
          "joyai": joyai.joyai_tiny,
          "afmoe": afmoe.afmoe_tiny,
          "mimo": mimo.mimo_tiny}
BACKENDS = ("gather", "pallas")
PROGRAMS = ("decode_1", "decode_4", "decode_8", "verify", "prefill_32",
            "chunk_16")
# a block length of 4: the tiers of 1, 4 and 8 tokens are one and two
# whole blocks (decode_<blocks>); no speculation beside a pending block
SDAR_PROGRAMS = ("decode_1", "decode_2", "prefill_32", "chunk_16")
CASES = [(blk, backend, prog) for blk in BLOCKS for backend in BACKENDS
         for prog in (SDAR_PROGRAMS if blk == "sdar" else PROGRAMS)
         if not (blk in ("lfm2", "afmoe", "mimo") and prog == "verify")]
# (no verify program: slot state, and window layers' rings)


def _scope_rows(jaxpr, under: str = "") -> list:
    """(primitive, chain of named scopes) of every equation of ``jaxpr``
    and of the jaxprs nested in it. A nested jaxpr is traced with an empty
    name stack and lowered under its equation's, so the chain of an inner
    equation is its outer equations' chains and its own, as a profile's op
    path has them; a ``jit`` inside adds its ``jit(<name>)`` as lowering
    does, a kernel call its kernel's name. Source lines are left out."""
    rows = []
    for eqn in jaxpr.eqns:
        here = "/".join(x for x in (under, str(eqn.source_info.name_stack))
                        if x)
        name = eqn.params.get("name")
        if "name_and_src_info" in eqn.params:
            name = eqn.params["name_and_src_info"].name
        prim = eqn.primitive.name + (f"[{name}]" if isinstance(name, str)
                                     else "")
        rows.append(f"{prim} @ {here}")
        inner = f"{here}/jit({name})".lstrip("/") \
            if eqn.primitive.name in ("pjit", "jit") else here
        for sub in jax.core.jaxprs_in_params(eqn.params):
            rows += _scope_rows(sub, inner)
    return rows


def _scope_hash(jaxpr) -> str:
    """A hash of the sorted multiset of :func:`_scope_rows`: it moves when
    an op moves to another scope (the benchmark's readers find ops by
    those paths, and the lowered text does not hold them) or a ``jit``
    boundary appears inside a step."""
    return hashlib.sha256(
        "\n".join(sorted(_scope_rows(jaxpr))).encode()).hexdigest()


def _traced(eng: LLMEngine, program: str):
    """The program traced, its operands built as the loop builds them
    (numpy, or the engine's device state): ``.lower().as_text()`` is its
    lowered text, ``.jaxpr`` what :func:`_scope_hash` reads."""
    kind, _, n = program.partition("_")
    w = eng.cfg.max_batch_size
    idx = eng._slot_index((), w)
    state = (eng.params, eng.kv, eng._pt_dev, eng._sl_dev, eng._dev_tokens,
             eng._rng, eng._temps_dev, idx)
    if kind == "decode":
        return eng._decode.trace(*state, int(n))
    if kind == "verify":
        return eng._verify.trace(*state, np.full(
            (w, eng.cfg.spec_draft_len), -1, np.int32))
    table = np.zeros((eng._table_width,), np.int32)   # with a ring, if any
    toks = np.zeros((1, int(n)), np.int32)
    tail = (eng._rng, np.zeros((1,), np.float32), np.int32(0))
    if kind == "prefill":
        return eng._prefill_fn(int(n)).trace(
            eng.params, eng.kv, eng._dev_tokens, table, toks, np.int32(5),
            *tail)
    return eng._chunk_fn(int(n)).trace(
        eng.params, eng.kv, eng._dev_tokens, table, toks, np.int32(0),
        np.int32(5), *tail, np.bool_(True))


@functools.cache
def _hashes(block: str, backend: str) -> dict:
    """One engine a (block, backend), built once a process; each of its
    programs traced once: (hash of the lowered text, hash of the scopes)."""
    eng = LLMEngine(LLMConfig(model_config=BLOCKS[block](),
                              attention_kernel=backend, **ENGINE))
    out = {}
    for b, k, prog in CASES:
        if (b, k) == (block, backend):
            traced = _traced(eng, prog)
            out[prog] = (hashlib.sha256(
                traced.lower().as_text().encode()).hexdigest(),
                _scope_hash(traced.jaxpr.jaxpr))
    return out


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        data = json.load(f)
    if data["jax"] != jax.__version__:
        pytest.skip(f"recorded under jax {data['jax']}, this is "
                    f"{jax.__version__}: rewrite {DATA}")
    return data


@pytest.mark.parametrize("block,backend,program", CASES,
                         ids=["-".join(c) for c in CASES])
def test_program_lowers_to_the_recorded_text(recorded, block, backend,
                                             program):
    assert _hashes(block, backend)[program][0] \
        == recorded["programs"][f"{block}-{backend}-{program}"], (
        "the program's lowered text changed: if that was meant, rewrite "
        "tests/data/engine_program_hashes.json (this file, run as a script)")


# What the last PR that MEANT to move a program recorded, a (block, backend):
# its (programs, scopes), every entry, as sorted JSON, hashed. ISSUE 56's
# tree wrote every value (each program ends in a sampler or a head, SDAR's
# prefill and chunk apart); until then three pins stood here, one for the
# dense block's twelve programs (ISSUE 50, 54), one for all but the six
# programs whose kernel writes the call's rows (ISSUE 53) and one a block
# (ISSUE 55), which after ISSUE 56 all pinned the same tree.
PINNED = {
    ("afmoe", "gather"): ("1544a414d8e6742a", "6177d2e6031680d3"),
    ("afmoe", "pallas"): ("2f58010608951db5", "007b490c9725bfe6"),
    ("dense", "gather"): ("aa52df80876c8bb4", "c0f09ae5f7cc0595"),
    ("dense", "pallas"): ("6404c648146edd0e", "5fb6e753f3f6df57"),
    ("joyai", "gather"): ("89ece47f8b6fb55a", "c1bf1e32aad2f305"),
    ("joyai", "pallas"): ("568c74a9c0c860a6", "fc40df3fb8a0c7b0"),
    ("lfm2", "gather"): ("b7eb04a908183266", "975dbc9f84da2a0c"),
    ("lfm2", "pallas"): ("e8485db8ac199b0a", "e9f2694add31644a"),
    ("mimo", "gather"): ("5cdd87e0cc9f6318", "09e384b219266a31"),
    ("mimo", "pallas"): ("1c16c6903021953c", "5d76f4b3adab3bc1"),
    ("sdar", "gather"): ("f9415abf6a4a2a8e", "761664c839cfab88"),
    ("sdar", "pallas"): ("9ac4149600c0cd8f", "356d33a4e647d20e"),
}


@pytest.mark.parametrize("block,backend", sorted(PINNED),
                         ids=["-".join(k) for k in sorted(PINNED)])
def test_no_program_is_recorded_again_unseen(recorded, block, backend):
    """A rewrite of the record (a block added, a program MEANT to move)
    cannot move another block's or backend's programs unseen: a PR that
    means to move these re-pins them here and says so in the header."""
    for key, want in zip(("programs", "scopes"), PINNED[block, backend]):
        mine = {k: v for k, v in recorded[key].items()
                if k.startswith(f"{block}-{backend}-")}
        assert hashlib.sha256(json.dumps(mine, sort_keys=True).encode()
                              ).hexdigest()[:16] == want, (block, backend, key)


@pytest.mark.parametrize("block,backend,program", CASES,
                         ids=["-".join(c) for c in CASES])
def test_program_keeps_every_op_under_its_recorded_scopes(recorded, block,
                                                          backend, program):
    """ISSUE 51: the layer of a served block is written once
    (``kv_cache._layer``); ``scopes`` was written by the parent's tree
    (commit 0fc9cab) before that file was touched. Scopes are not in the
    lowered text, nor in the compile cache's key, and the benchmark's
    readers find a device op by them."""
    assert _hashes(block, backend)[program][1] \
        == recorded["scopes"][f"{block}-{backend}-{program}"], (
        "an op of the program moved to another chain of named scopes (or "
        "a jit boundary moved): if that was meant, rewrite the record "
        "(this file, run as a script, with --scopes)")


def test_expert_visits_follow_the_kernels_rule_on_a_hand_made_record():
    """``_experts_touched`` [1]: the times one grouped product passes an
    expert's matrix through the MXU, from the routing record by the
    kernel's rule: 1 a touched expert, 1 more for each further 128 rows.
    130 rows of one pick over 4 experts: expert 2 takes 129 (two visits),
    expert 0 one (one visit), experts 1 and 3 none; the second layer puts
    128 on expert 3 (one visit) and one each on 0 and 1."""
    import types

    import jax.numpy as jnp

    first = np.full((130, 1), 2, np.int32)
    first[7] = 0
    second = np.full((130, 1), 3, np.int32)
    second[0], second[129] = 0, 1
    eng = types.SimpleNamespace(
        _jax=jax, _jnp=jnp, cfg=types.SimpleNamespace(max_batch_size=130),
        _cache_spec=types.SimpleNamespace(routed_layers=2, n_experts=4,
                                          top_k=1))
    idx = jnp.arange(130, dtype=jnp.int32)                  # every lane live
    touched, visits = np.asarray(LLMEngine._experts_touched(
        eng, {"routing": jnp.asarray(np.stack([first, second]))}, idx))
    assert (touched, visits) == (2 + 3, (2 + 1) + (1 + 1 + 1))


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
    # every key is rewritten, or with --scopes the scopes alone (the
    # programs' text as it is recorded: a PR that says "nothing lowers
    # to another text" leaves that key to the tree that wrote it)
    out = {"jax": jax.__version__, "programs": {}, "scopes": {}}
    for blk in BLOCKS:
        for kernel in BACKENDS:
            for name, (text, scopes) in _hashes(blk, kernel).items():
                out["programs"][f"{blk}-{kernel}-{name}"] = text
                out["scopes"][f"{blk}-{kernel}-{name}"] = scopes
    if "--scopes" in sys.argv[1:]:
        with open(DATA) as f:
            was = json.load(f)
        assert was["jax"] == out["jax"], (was["jax"], out["jax"])
        out["programs"] = was["programs"]
    os.makedirs(os.path.dirname(DATA), exist_ok=True)
    with open(DATA, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"{len(out['programs'])} programs -> {DATA}")
