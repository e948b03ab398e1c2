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
same tree. ISSUE 58 makes the number of steps (or whole blocks) of a
dispatch an OPERAND of the decode program: the 34 recorded decode programs
(``decode_1/4/8``, SDAR's ``decode_1/2``) are 12, one a (block, backend),
recorded under ``decode`` and pinned again; what the 34 were is held in
their place by ``test_the_operand_program_at_k_is_a_scan_of_k_steps``: the
one program run at k against a ``lax.scan`` of k of the same step, on
operands a live engine dispatched, bit for bit. Every OTHER program
(verify, prefill, chunk: 28) lowers to the parent's text under the parent's
scopes, and ``PARENT_57`` holds that apart from ``PINNED``. ISSUE 60 ADDS
the block that runs a state-space mixer beside attention in every layer
(``falcon``: six programs, no verify; its state arrays a layer, its decode
programs with the in-place update, on the pallas backend the kernel) and
leaves the 40 others as they were recorded: that PR's edits of kv_cache.py
(the state pool's rows, a mixer kind, the whole prefill's branch) lower
every one of them to the parent's text under the parent's scopes. ISSUE 61
moves the decode and verify calls of a block WITHOUT window layers to the
walking body (one entry of ``paged_attention.WALKS_LIVE``), whose kernel
writes the call's rows, and cuts that body's index arithmetic (``_div`` /
``_rem``: one equation a division of an index that is never negative,
where ``//`` and ``%`` lowered a dozen; the rows a tile keeps found in
``min(t_span, 16)`` steps, not 16): NINE programs of the pallas backend
were recorded again, text and scopes, every one that calls the walking
body (``dense-decode``, ``dense-verify``, ``lfm2-decode``, ``falcon-decode``:
the kernel under ``jit(_gqa_walk_call)``, no row scatter under
``kv_write``; ``sdar-decode``, ``afmoe-decode``, ``afmoe-chunk_16``,
``mimo-decode``, ``mimo-chunk_16``: the same calls, the shorter body), their
six pins with them and three entries of ``PARENT_57`` (the dense block's
verify program, afmoe's and mimo's chunk programs); the 37 others (every
program of the gather backend, every prefill, the latent block, the chunk
programs that run the grid body) are the parent's, letter for letter.

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

from ray_tpu.models import (afmoe, falcon_h1, joyai, lfm2_moe,  # noqa: E402
                            llama, mimo, sdar_moe)
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
          "mimo": mimo.mimo_tiny,
          "falcon": falcon_h1.falcon_h1_tiny}
BACKENDS = ("gather", "pallas")
PROGRAMS = ("decode", "verify", "prefill_32", "chunk_16")
# (no speculation beside a pending block)
SDAR_PROGRAMS = ("decode", "prefill_32", "chunk_16")
CASES = [(blk, backend, prog) for blk in BLOCKS for backend in BACKENDS
         for prog in (SDAR_PROGRAMS if blk == "sdar" else PROGRAMS)
         if not (blk in ("lfm2", "afmoe", "mimo", "falcon")
                 and prog == "verify")]
# (no verify program: slot state, and window layers' rings)
# every k a dispatch can run (the tiers of 1, 4 and 8 tokens: steps, or with
# SDAR's block length of 4 one and two whole blocks): the parent held a
# program for each
STEP_CASES = [(blk, backend, k) for blk in BLOCKS for backend in BACKENDS
              for k in ((1, 2) if blk == "sdar" else (1, 4, 8))]


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
        return eng._decode.trace(*state, np.int32(1))
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
def _engine(block: str, backend: str) -> LLMEngine:
    """One engine a (block, backend), built once a process."""
    return LLMEngine(LLMConfig(model_config=BLOCKS[block](),
                               attention_kernel=backend, **ENGINE))


@functools.cache
def _hashes(block: str, backend: str) -> dict:
    """Each program of the (block, backend)'s engine traced once: (hash of
    the lowered text, hash of the scopes)."""
    eng = _engine(block, backend)
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
# its (programs, scopes), every entry, as sorted JSON, hashed. ISSUE 58's
# tree wrote the decode entry of each (the steps of a dispatch became an
# operand); every other entry is ISSUE 56's tree's (each program ends in a
# sampler or a head, SDAR's prefill and chunk apart), which ``PARENT_57``
# below holds on its own. ISSUE 61's tree wrote the pallas backend's entries
# that call the walking body again (its header paragraph): ``dense`` and
# ``lfm2`` walk and write in their decode (and verify) programs, and the
# body's index arithmetic is shorter for ``sdar``, ``afmoe`` and ``mimo`` too.
PINNED = {
    ("afmoe", "gather"): ("7335753175bc5025", "718e71920d0fbe71"),
    ("afmoe", "pallas"): ("e7a589dc891935d7", "9daa650e8f5cd520"),
    ("dense", "gather"): ("9d39e962040c8f7c", "bc2127eb6ec59536"),
    ("dense", "pallas"): ("ded2fe8c05def00e", "726fda2220990466"),
    ("joyai", "gather"): ("bc56b071827794c3", "d1e09a603e6d8b2c"),
    ("joyai", "pallas"): ("312034029fb07bda", "063d50367ea3e23a"),
    ("lfm2", "gather"): ("e0aeff7770d874dc", "c7818c674526ef2d"),
    ("lfm2", "pallas"): ("afc016ca4a413020", "8b2c509b37a5836f"),
    ("mimo", "gather"): ("2e94649e0b0c148c", "60d19042b4136686"),
    ("mimo", "pallas"): ("67ef1f3a7e05a3a2", "487df88c0654797e"),
    ("sdar", "gather"): ("1cfca4cb8846a3e2", "0f8308efca9ee145"),
    ("sdar", "pallas"): ("3b396836bcf5318e", "429b3e47041217fc"),
}
# ISSUE 60's tree wrote the new block's entries (ISSUE 61's its decode
# program on the pallas backend again); it is no part of the parent's
# record (``PARENT_57`` runs over ``PINNED``'s keys)
ADDED_60 = {
    ("falcon", "gather"): ("e32d807d32d177aa", "ff990a5d03fd0166"),
    ("falcon", "pallas"): ("ffd057cea1734dcd", "e5e43c32ceca854b"),
}


def _pin(data: dict, block: str, backend: str, decode: bool = True) -> tuple:
    """(programs, scopes) of a (block, backend) in a record, each as sorted
    JSON, hashed; without ``decode``, every program but the decode one."""
    return tuple(hashlib.sha256(json.dumps(
        {k: v for k, v in data[key].items()
         if k.startswith(f"{block}-{backend}-")
         and (decode or not k.endswith("-decode"))},
        sort_keys=True).encode()).hexdigest()[:16]
        for key in ("programs", "scopes"))


@pytest.mark.parametrize("block,backend", sorted(PINNED),
                         ids=["-".join(k) for k in sorted(PINNED)])
def test_no_program_is_recorded_again_unseen(recorded, block, backend):
    """A rewrite of the record (a block added, a program MEANT to move)
    cannot move another block's or backend's programs unseen: a PR that
    means to move these re-pins them here and says so in the header."""
    assert _pin(recorded, block, backend) == PINNED[block, backend]


@pytest.mark.parametrize("block,backend", sorted(ADDED_60),
                         ids=["-".join(k) for k in sorted(ADDED_60)])
def test_the_block_added_since_is_pinned_too(recorded, block, backend):
    assert _pin(recorded, block, backend) == ADDED_60[block, backend]


# What commit d784841's record (ISSUE 56's tree wrote it) holds for every
# program BUT the decode ones, a (block, backend): ISSUE 58 recorded the
# decode programs again (the steps became an operand) and no other;
# ISSUE 61 the dense block's verify program on the pallas backend (its
# call walks and writes) and afmoe's and mimo's chunk programs (the walking
# body's shorter index arithmetic), so those three entries are its tree's.
PARENT_57 = {
    ("afmoe", "gather"): ("0318071c036f5855", "3d593530f9433595"),
    ("afmoe", "pallas"): ("bb8a1d45dd918a04", "1187ad2afae06848"),
    ("dense", "gather"): ("8cc48a604031adb5", "9985945bacdc9d7c"),
    ("dense", "pallas"): ("1e203700384c43f3", "06d7f3b4a6bf48bd"),
    ("joyai", "gather"): ("4f178b70acd4a9ec", "9b8c3dc9658ba592"),
    ("joyai", "pallas"): ("00ad5719e83a4852", "d36039e595c6f124"),
    ("lfm2", "gather"): ("30e49160fb2f0a39", "01e934d82a65d88d"),
    ("lfm2", "pallas"): ("4be70bf6cf86ac2d", "fe815c648e186b72"),
    ("mimo", "gather"): ("391eeec10946fb37", "9db93c3d19a79156"),
    ("mimo", "pallas"): ("80b8feaade6ced89", "811463c329833ffd"),
    ("sdar", "gather"): ("c5917877db65c058", "2a97e54a9534c8db"),
    ("sdar", "pallas"): ("c8e7a767c83aef5b", "51cd9b00ecef8e66"),
}


@pytest.mark.parametrize("block,backend", sorted(PINNED),
                         ids=["-".join(k) for k in sorted(PINNED)])
def test_verify_prefill_and_chunk_lower_to_the_parents_text(recorded, block,
                                                            backend):
    assert _pin(recorded, block, backend, decode=False) \
        == PARENT_57[block, backend]


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


def _equal(got, want, what: str):
    """Two trees of arrays, bit for bit (a key by its raw words)."""
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want), what
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, what
        assert a.tobytes() == b.tobytes(), what


PROMPTS = ((5, 0.0), (19, 1.0), (12, 0.7))      # (tokens, temperature)


def _submit(eng: LLMEngine, max_tokens: int, greedy: bool = False) -> list:
    """PROMPTS handed to ``eng``; the requests' ids."""
    rs = np.random.RandomState(58)
    return [eng.submit(rs.randint(1, 500, size=n).tolist(),
                       temperature=0.0 if greedy else temp,
                       max_tokens=max_tokens) for n, temp in PROMPTS]


@functools.cache
def _live(block: str, backend: str):
    """(engine, the operands of a decode dispatch of its loop as host
    arrays): three streams, one greedy and two that sample, prefilled and
    a few dispatches into their decode, the loop driven on this thread.
    What the loop hands ``_decode`` is copied before the program takes it
    (the pool, lengths and tokens are donated)."""
    eng = _engine(block, backend)
    _submit(eng, max_tokens=64)
    real, seen = eng._decode, []

    def spy(*operands):
        seen.append(jax.tree.map(np.asarray, operands[1:8]))
        return real(*operands)

    eng._decode = spy
    for _ in range(60):
        eng._loop_pass()
        if len(seen) >= 4 and (seen[-1][-1] != eng.cfg.max_batch_size
                               ).sum() == len(PROMPTS):
            break
    else:
        raise AssertionError("the loop never decoded the three streams")
    eng._decode = real
    return eng, seen[-1]


def _scan_of(eng: LLMEngine, k: int):
    """The program the parent compiled for a static k: the state gathered,
    ``lax.scan`` of k of the engine's own step, the state scattered."""
    import jax.numpy as jnp

    blocks = eng._block_len > 1
    trash = eng.cfg.max_batch_size

    def program(params, kv, pt_full, sl_full, toks_full, rng, temps_full,
                idx):
        one = (eng._block_one if blocks else eng._decode_one)(
            params, pt_full[idx], temps_full[idx], idx)

        def step(carry, _):
            carry, toks, counts = one(carry)
            return carry, (toks, counts)

        (kv, lens, last, rng), (toks, counts) = jax.lax.scan(
            step, (kv, sl_full[idx], toks_full[idx], rng), None, length=k)
        if blocks:                                          # [k x B, W]
            toks = jnp.swapaxes(toks, 1, 2).reshape(k * eng._block_len, -1)
        out = (toks, toks_full.at[idx].set(last), kv,
               sl_full.at[idx].set(jnp.where(idx == trash, 0, lens)), rng)
        return out if counts is None else out + (jnp.sum(counts, axis=0),)

    return jax.jit(program)


@pytest.mark.parametrize("block,backend,k", STEP_CASES,
                         ids=[f"{b}-{be}-k{k}" for b, be, k in STEP_CASES])
def test_the_operand_program_at_k_is_a_scan_of_k_steps(block, backend, k):
    """ISSUE 58: the width's ONE decode program, handed k as an operand,
    returns the tokens (rows [:k] of its buffer; the rest are never read),
    the carried tokens, the pool, the lengths, the key and a routed
    block's counts that the parent's program of a static k (a scan of k
    steps) returns from the same operands, bit for bit."""
    eng, operands = _live(block, backend)
    got = eng._decode(eng.params, *operands, np.int32(k))
    want = _scan_of(eng, k)(eng.params, *operands)
    rows = k * eng._block_len
    assert got[0].shape[0] == eng._blocks_of(ENGINE["decode_block"]) \
        * eng._block_len >= rows
    _equal(got[0][:rows], want[0], "tokens")
    for g, w, what in zip(got[1:], want[1:], (
            "carried tokens", "pool", "lengths", "key", "counts")):
        _equal(g, w, what)
    assert len(got) == len(want)
    # (the dispatch sampled: a live row asked for a temperature)
    assert (operands[5][operands[6]] > 0).any()


@pytest.mark.parametrize("block", ["dense", "lfm2", "sdar"])
def test_harvest_reads_only_the_rows_of_its_k(block):
    """The program's buffer has the ceiling tier's rows whatever k a
    dispatch ran: with every row past k poisoned before the harvest sees
    the buffer, the streams are what they were."""
    streams = []
    for poison in (False, True):
        eng = LLMEngine(LLMConfig(model_config=BLOCKS[block](),
                                  attention_kernel="gather", **ENGINE))
        rids = _submit(eng, max_tokens=12, greedy=True)
        real, short = eng._decode, []

        def spoiled(*operands, real=real, eng=eng, short=short):
            toks, *rest = real(*operands)
            rows = int(operands[8]) * eng._block_len
            short.append(rows < toks.shape[0])
            return (toks.at[rows:].set(-1), *rest)

        if poison:
            eng._decode = spoiled
        for _ in range(200):
            eng._loop_pass()
            if all(eng._requests[r].done for r in rids):
                break
        streams.append([eng.result(r, timeout=1)["tokens"] for r in rids])
        assert not poison or any(short)
    assert streams[0] == streams[1]
    assert all(s and min(s) >= 0 for s in streams[1])


TIER_CASES = [("dense", {}), ("dense", {"spec_decode_enabled": True}),
              ("lfm2", {}), ("sdar", {}), ("joyai", {}), ("afmoe", {}),
              ("mimo", {}), ("falcon", {})]


@pytest.mark.parametrize("block,over", TIER_CASES, ids=[
    "dense", "dense-spec", "lfm2", "sdar", "joyai", "afmoe", "mimo",
    "falcon"])
def test_every_k_the_loop_can_pick_is_one_start_warmed(block, over):
    """``_warmup_decode_programs`` dispatches ONE decode program a bucket
    width (ISSUE 58: k is its operand), at the ceiling tier's k, and
    leaves the loop's key as it was. Every k ``_select_block`` can return,
    in every state of the queue and at every tier of the idle lead (ISSUE
    42), is at most the rows of that program's buffer, and a dispatch of
    the warmed program at each such k enters no compile. Without
    speculation (which caps the idle tier) the loop reaches every tier."""
    from ray_tpu.serve.llm import lead as lead_mod

    eng = LLMEngine(LLMConfig(model_config=BLOCKS[block](),
                              attention_kernel="gather",
                              **{**ENGINE, **over}))
    real, warmed = eng._decode, []

    def decode(*operands):          # k is the program's last operand
        warmed.append((operands[7].shape[0], int(operands[8])))
        return real(*operands)

    eng._decode = decode
    key = np.asarray(eng._rng).copy()
    eng._warmup_decode_programs()
    eng._decode = real
    np.testing.assert_array_equal(np.asarray(eng._rng), key)
    rows = eng._blocks_of(ENGINE["decode_block"])
    widths = sorted({eng._bucket_width(n)
                     for n in range(1, ENGINE["max_batch_size"] + 1)})
    assert warmed == [(w, rows) for w in widths]
    assert real._cache_size() == len(widths)
    stats = eng.engine_stats()
    assert stats["decode_programs"] == len(widths)
    assert stats["compile_events"] > 0
    picked = set()
    for _tier in eng._lead.tiers:
        for waiting, free, prefilling in (([], [0], []), ([object()], [0], []),
                                          ([object()], [], []),
                                          ([], [0], [object()])):
            eng._waiting, eng.free_slots = waiting, free
            eng._prefilling = prefilling
            picked.add(eng._select_block())
        # (a dry dispatch under a full collection does not count)
        for _ in range(8 * lead_mod.CLIMB_DRY):
            if eng._lead.k != _tier:
                break
            eng._lead.observe(1, eng._collector.pause_n)
    eng._waiting, eng._prefilling = [], []
    assert eng._lead.k == rows
    tiers = {eng._blocks_of(1), eng._blocks_of(4), rows}
    if over:        # speculation caps the idle tier at a draft's length
        tiers.add(ENGINE["spec_draft_len"])
    assert picked <= tiers and max(picked) <= rows, (picked, tiers)
    if not over:
        assert picked == tiers
    # a dispatch at every such k, as the loop builds it, is the warmed
    # program: nothing is traced or compiled again
    events = stats["compile_events"]
    for w in widths:
        for k in sorted(picked):
            with eng._prof.compile_scope("decode", ("decode", w),
                                         mid_traffic=True):
                toks, eng._dev_tokens, eng.kv, eng._sl_dev, _key, *_n = \
                    eng._decode(eng.params, eng.kv, eng._pt_dev, eng._sl_dev,
                                eng._dev_tokens, eng._rng, eng._temps_dev,
                                eng._slot_index((), w), np.int32(k))
            assert toks.shape[0] == rows * eng._block_len
    assert real._cache_size() == len(widths)
    stats = eng.engine_stats()
    assert stats["mid_traffic_compiles"] == 0
    assert stats["compile_events"] == events
    assert stats["decode_programs"] == len(widths)


if __name__ == "__main__":
    # every key is rewritten, or with --scopes the scopes alone (the
    # programs' text as it is recorded: a PR that says "nothing lowers
    # to another text" leaves that key to the tree that wrote it)
    out = {"jax": jax.__version__, "programs": {}, "scopes": {}}
    for blk in BLOCKS:
        if "--only" in sys.argv[1:] \
                and blk != sys.argv[sys.argv.index("--only") + 1]:
            continue
        for kernel in BACKENDS:
            for name, (text, scopes) in _hashes(blk, kernel).items():
                out["programs"][f"{blk}-{kernel}-{name}"] = text
                out["scopes"][f"{blk}-{kernel}-{name}"] = scopes
    if "--only" in sys.argv[1:]:
        # ``--only <block>``: that block's entries alone are written; every
        # other entry stays the tree's that wrote it, letter for letter
        only = sys.argv[sys.argv.index("--only") + 1]
        with open(DATA) as f:
            was = json.load(f)
        assert was["jax"] == out["jax"], (was["jax"], out["jax"])
        for key in ("programs", "scopes"):
            out[key] = {**was[key], **{k: v for k, v in out[key].items()
                                       if k.startswith(f"{only}-")}}
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
    for name, decode in (("PINNED", True), ("every program but decode", False)):
        print(name, "= {")
        for blk, kernel in sorted({**PINNED, **ADDED_60}):
            print(f"    {(blk, kernel)!r}: {_pin(out, blk, kernel, decode)!r},")
        print("}")
