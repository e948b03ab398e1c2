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
c3e050f's, letter for letter, and ``PARENT_DENSE`` below holds them a
second time so that a rewrite of the file cannot move them unseen: no
Mistral cell runs a changed program. ISSUE 52 ADDS the block with window
layers (``afmoe``: ten programs, no verify) and leaves the 42 others as
they were recorded: that PR's edits of kv_cache.py and of the walking
kernel lower every one of them to the parent's text. ISSUE 53 records
again the SIX programs that call the walking body on pools of K and V
(SDAR's two block programs, Trinity's three decode tiers and its chunk, on
the pallas backend: their kernel writes the call's rows and nothing is
scattered before it); ``PARENT_53`` holds the other 46 to the parent's.
ISSUE 54 records again the THIRTY programs of the dense block, afmoe and
SDAR (their q / k / v / gate projections are held head-major and read by
``"btd,hdk->bthk"``: the text's parameter shapes and that einsum's name among
the scopes move, no equation is added or taken away) and leaves the
twenty-two of LFM2 and JoyAI to the parent's text and scopes. ISSUE 55 ADDS
the block whose key and value rows differ in width and whose layer kinds
differ in KV heads and in a sink (``mimo``: ten programs, no verify) and
leaves the 52 others as they were recorded (``PARENT_54``): that PR's edits
of kv_cache.py and of the walking kernel (a value width, ``sink=``) lower
every one of them to the parent's text.

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
        np.int32(5), *tail)


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


# the dense block's programs as ISSUE 54's tree lowered them (until then
# commit c3e050f's, PR 48): ``wq_hm`` / ``wk_hm`` / ``wv_hm`` [L, H, D, hd]
# under ``"btd,hdk->bthk"``, nothing else of the text moved
PARENT_DENSE = {
    "dense-gather-chunk_16":
        "2b2f6ba7595da43054ae09b247b64e792a2ed5367d52f39cd5d63cf38bb86e57",
    "dense-gather-decode_1":
        "ef76820ef20329918e4a99e7cac28a26299d611961e6fc3f8fa49edc7a077b39",
    "dense-gather-decode_4":
        "35ce70162fac9f4c2e1434243dfe3a1d42bf00291cfb2ff6d00dd73049879fac",
    "dense-gather-decode_8":
        "df74eabae488f13b777d3cf040d1528deaa0a61cac10d843cfbc543abf058134",
    "dense-gather-prefill_32":
        "4f2c18d4e7663d6562abfd793e412cbb6cc4e635114ae0ffcfe422e2d795124d",
    "dense-gather-verify":
        "c43f030ed3315c8bf7e011c33b21b9a0c1e18c8a76654a7bcf016605b20bc4ef",
    "dense-pallas-chunk_16":
        "d1566d6d905ff94019524b3cdee83e3f9fa137ff24a37be36c135ceda05edb90",
    "dense-pallas-decode_1":
        "16ac56ffd7a76c580e6916e59b372c633da1f48810c8f38a0c88a2026c7b8e5b",
    "dense-pallas-decode_4":
        "418ead806944b131cfb5ad2790ebf3d230b8f28fbeb6cad6f5afc2335a08eace",
    "dense-pallas-decode_8":
        "d5e10eb75cbad1f7d569dd3497f2e82e00bf4664864ab10a7b665326879f980e",
    "dense-pallas-prefill_32":
        "4f2c18d4e7663d6562abfd793e412cbb6cc4e635114ae0ffcfe422e2d795124d",
    "dense-pallas-verify":
        "e7a06a0d750b8038a59b75330fe6e388b1af658ccbef3e05e8f3600ddb5b6c34",
}


@pytest.mark.parametrize("name", sorted(PARENT_DENSE))
def test_a_dense_program_is_recorded_as_the_parent_lowered_it(recorded, name):
    """ISSUE 50 rewrote the routed families' entries; a dense entry that
    moved with them would mean the Mistral cells run another program.
    ISSUE 54 MEANT to move them (the served projections head-major) and
    pinned what its tree lowered."""
    assert recorded["programs"][name] == PARENT_DENSE[name]


# ISSUE 53: the programs that call the walking body on pools of K and V,
# whose kernel now writes the call's rows (kv_cache._write_read), were
# recorded again by that PR's tree ...
REWRITTEN_53 = {"sdar-pallas-decode_1", "sdar-pallas-decode_2",
                "afmoe-pallas-decode_1", "afmoe-pallas-decode_4",
                "afmoe-pallas-decode_8", "afmoe-pallas-chunk_16"}
# ... and every other entry is commit f0bf47b's (PR 52), letter for letter:
# a block's (programs, scopes) without those six, as sorted JSON, hashed
# ISSUE 54 (the attention projections of the dense block, afmoe and SDAR
# head-major) recorded those three blocks' programs again, every one: their
# rows are that PR's tree's, the einsum's own name (``btd,hdk->bthk``) the
# only scope that moved; the LFM2 and JoyAI rows are STILL f0bf47b's, which
# is the test that those two cells bypass the change
PARENT_53 = {"dense": ("7915703ca84ad9bf", "1d580735eeb2efa6"),
             "lfm2": ("9d40ee4fdcb69a1c", "f6677a7de16ef7bf"),
             "joyai": ("3abdaec215c28aaa", "db96b92a84c5ddd7"),
             "sdar": ("a276d9c95613b27d", "664acd450d35de00"),
             "afmoe": ("fb165ad26588ac5e", "e7005cf1a4e73aa1")}


@pytest.mark.parametrize("block", sorted(PARENT_53))
def test_only_the_programs_whose_kernel_writes_were_recorded_again(recorded,
                                                                   block):
    """The dense, LFM2 and latent programs, SDAR's on the gather backend
    and its prefill and chunk, Trinity's on the gather backend and its
    whole prefill lower to the parent's text under the parent's scopes: no
    Mistral, LFM2 or JoyAI cell runs a changed program, and a rewrite of
    the file cannot move one unseen."""
    for key, want in zip(("programs", "scopes"), PARENT_53[block]):
        others = {k: v for k, v in recorded[key].items()
                  if k.startswith(block + "-") and k not in REWRITTEN_53}
        assert hashlib.sha256(json.dumps(others, sort_keys=True).encode()
                              ).hexdigest()[:16] == want, (block, key)


# ISSUE 55 added the block "mimo" and recorded nothing else again: a block's
# (programs, scopes), EVERY entry, as sorted JSON, hashed, are commit
# 6dba7c7's (PR 54)
PARENT_54 = {"dense": ("7915703ca84ad9bf", "1d580735eeb2efa6"),
             "lfm2": ("9d40ee4fdcb69a1c", "f6677a7de16ef7bf"),
             "joyai": ("3abdaec215c28aaa", "db96b92a84c5ddd7"),
             "sdar": ("2421318f7c41876d", "1d14140e23f85b6e"),
             "afmoe": ("07c619f41e8f0c75", "d6bbdc8add88db0d")}


@pytest.mark.parametrize("block", sorted(PARENT_54))
def test_a_new_block_records_no_accepted_program_again(recorded, block):
    """Every program of the blocks the accepted cells run lowers to the
    parent's text under the parent's scopes: a rewrite of the file that
    adds a block cannot move one of them unseen."""
    for key, want in zip(("programs", "scopes"), PARENT_54[block]):
        mine = {k: v for k, v in recorded[key].items()
                if k.startswith(block + "-")}
        assert hashlib.sha256(json.dumps(mine, sort_keys=True).encode()
                              ).hexdigest()[:16] == want, (block, key)


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
