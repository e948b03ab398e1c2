"""No program of the engine re-lays an attention projection out (ISSUE 54).

A projection stored ``[in, H, hd]`` and read by ``"btd,dhk->bthk"`` is copied
by the v5e compiler before its product may read it (heads outermost): once an
execution for a walked layer, once a layer of every step for a scanned stack
(its slice fused into the copy), the whole stack at entry under a loop of
steps. Held head-major (models/block.py ``head_major``, each block's
``serve_params``) the product reads the parameter in place. Here the engine's
own programs (``LLMEngine._decode_impl`` / ``_block_impl`` and a chunk
program, on a stand-in for the engine, the Pallas backend compiled and not
interpreted) are compiled for a DESCRIBED v5e at the cells' widths and depth
2, the pool donated as the engine donates it, and the compiled module is
listed: no ``copy``, standing alone or inside a product's fusion, and no
fusion of its own writes an array of a projection's shape. One case hands a
program the leaves as a checkpoint lays them: the listing must find the
conversion there, or it proves nothing. The dense block's chunk of 512 rows is
the one program left with a copy, as on the parent: under that many rows the
compiler wants the contraction minor, whichever way the stack lies.

Not a chip run: no time comes from it.
"""

import functools
import os
import re
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import afmoe, falcon_h1, llama, mimo, sdar_moe
from ray_tpu.models.block import HEAD_MAJOR, block_of
from ray_tpu.ops import paged_attention as paged_ops
from ray_tpu.serve.llm import kv_cache as kvc
from ray_tpu.serve.llm.engine import LLMEngine

PAGE = 128
# the cells' widths (benchmark/configs/*.json), depth 2
MISTRAL = dict(
    name="mistral", model=lambda: llama.LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, max_seq_len=2048, rope_theta=1e6, dtype=jnp.bfloat16),
    batch=32, pages=432, seq=2048, chunk=512)
TRINITY = dict(
    name="trinity", model=lambda: afmoe.AfmoeConfig(
        n_layers=2, n_dense=1, experts_held=8),
    batch=24, pages=512, seq=16384, chunk=512)
SDAR = dict(
    name="sdar", model=lambda: sdar_moe.SdarMoeConfig(n_layers=2),
    batch=64, pages=256, seq=2048, chunk=512)
# ISSUE 55: a full layer and two window layers (4 and 8 KV heads, q / k of
# 192 beside v of 128: ``wq_hm`` [64, 4096, 192], ``wv_hm`` [Hkv, 4096, 128])
MIMO = dict(
    name="mimo", model=lambda: mimo.MimoConfig(
        n_layers=3, pattern=(0, 1, 1), moe_freq=(0, 1, 1), experts_held=8,
        max_seq_len=9216),
    batch=48, pages=512, seq=9216, chunk=512)
# ISSUE 60: a state-space mixer beside attention in every layer (20 query
# heads on 4 KV heads of 128; a state pool of a row a slot: 97 rows of
# [32, 256, 128] float32 a layer)
FALCON = dict(
    name="falcon", model=lambda: falcon_h1.FalconH1Config(n_layers=2),
    batch=96, pages=1153, seq=2048, chunk=512)
# (cell, program, width, k): k the drafts of a verify round, 0 elsewhere.
# The steps (or whole blocks) of a dispatch are an operand of the decode
# program since ISSUE 58, one program a width: where the parent's k of 1
# and 8 stood, two of the cell's bucket widths do
CASES = [(MISTRAL, "decode", 4, 0), (MISTRAL, "decode", 16, 0),
         (MISTRAL, "decode", 32, 0),
         (TRINITY, "decode", 24, 0), (TRINITY, "decode", 8, 0),
         (TRINITY, "chunk", 512, 0),
         (SDAR, "decode", 64, 0),
         (MIMO, "decode", 48, 0), (MIMO, "decode", 16, 0),
         (MIMO, "chunk", 512, 0),
         (FALCON, "decode", 96, 0), (FALCON, "chunk", 512, 0)]
IDS = [f"{cell['name']}-{prog}-w{w}-k{k}" for cell, prog, w, k in CASES]


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e 2x2 (tests/test_flash_attention.py's
    ``v5e_2x2``: described here and never at import)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _stand_in(cell, backend="pallas", page=PAGE):
    """What the engine's program bodies read off the engine."""
    cfg = cell["model"]()
    spec = block_of(cfg).cache_spec(cfg)
    eng = types.SimpleNamespace(
        _jax=jax, _jnp=jnp, _kvc=kvc, model_cfg=cfg, _mesh=None,
        _attn_backend=backend, _cache_spec=spec,
        _block_len=spec.block_length,
        cfg=types.SimpleNamespace(page_size=page, top_k=0, decode_block=8,
                                  max_batch_size=cell["batch"]))
    for name in ("_experts_touched", "_blocks_of", "_run_steps",
                 "_decode_one", "_block_one"):
        setattr(eng, name, functools.partial(getattr(LLMEngine, name), eng))
    eng._pending_block = functools.partial(LLMEngine._pending_block, eng)
    eng._prefill_cache = {}
    return eng


def _compiled_text(cell, program, width, k, one_chip, served=True) -> tuple:
    """(the compiled module's text, the shapes of the projections); the
    Pallas kernels lowered as on the chip, not for the interpreter.
    ``program``: the engine's ``decode`` (``_decode_impl`` / ``_block_impl``),
    ``verify``, ``prefill`` and ``engine_chunk`` (``_prefill_fn`` /
    ``_chunk_fn`` of ``width`` tokens), or ``chunk``: the layers and the
    head as ``kv_cache.paged_prefill_chunk`` composes them (the
    benchmark's adapters' program: the head whatever the chunk)."""
    eng = _stand_in(cell)
    cfg, blk = eng.model_cfg, block_of(eng.model_cfg)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    checkpoint = jax.eval_shape(
        lambda: blk.init_params(jax.random.PRNGKey(0), cfg))
    in_served_form = jax.eval_shape(lambda p: blk.serve_params(p, cfg),
                                    checkpoint)
    params = shaped(in_served_form if served else checkpoint)
    ring = kvc.ring_pages(eng._cache_spec.window, PAGE, cell["chunk"]) \
        if eng._cache_spec.window else 0
    table = cell["seq"] // PAGE + ring
    b = cell["batch"]
    kv = shaped(jax.eval_shape(lambda: kvc.init_paged_cache(
        cfg, cell["pages"], PAGE, window_pages=b * ring + 1 if ring else 0,
        state_rows=b + 1 if eng._cache_spec.state_per_slot else 0)))
    toks = arg(b + 1, eng._block_len) if eng._block_len > 1 else arg(b + 1)
    key = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    state = (params, kv, arg(b + 1, table), arg(b + 1), toks, key,
             arg(b + 1, dtype=jnp.float32), arg(width))
    tail = (key, arg(1, dtype=jnp.float32), arg())      # rng, temp, slot
    with mock.patch.object(paged_ops, "interpret_default", lambda: False):
        if program == "decode":
            impl = LLMEngine._block_impl if eng._block_len > 1 \
                else LLMEngine._decode_impl
            fn = jax.jit(functools.partial(impl, eng),
                         donate_argnums=(1, 3, 4))
            lowered = fn.lower(*state, arg())           # ..., the steps
        elif program == "verify":
            fn = jax.jit(functools.partial(LLMEngine._verify_impl, eng),
                         donate_argnums=(1, 3, 4))
            lowered = fn.lower(*state, arg(width, k))
        elif program == "prefill":
            lowered = LLMEngine._prefill_fn(eng, width).lower(
                params, kv, toks, arg(table), arg(1, width), arg(), *tail)
        elif program == "engine_chunk":
            lowered = LLMEngine._chunk_fn(eng, width).lower(
                params, kv, toks, arg(table), arg(1, width), arg(), arg(),
                *tail, arg(dtype=jnp.bool_))            # ..., final
        else:
            fn = jax.jit(lambda p, kv, t, x, s, n: kvc.paged_prefill_chunk(
                p, kv, t, x, s, n, cfg, PAGE, "pallas"), donate_argnums=(1,))
            lowered = fn.lower(params, kv, arg(table), arg(1, width), arg(),
                               arg())
    projections = {
        tuple(leaf.shape) for path, leaf in
        jax.tree_util.tree_leaves_with_path(in_served_form)
        if str(getattr(path[-1], "key", "")).endswith(HEAD_MAJOR)}
    return lowered.compile().as_text(), projections


_WRITES = re.compile(r"= \(?(\w+)\[([\d,]*)\]\S* (copy|fusion)\(")


def weight_shaped_writes(text: str, projections) -> list:
    """The ``copy`` and fusion instructions of a compiled module whose
    output has a projection's dimensions in any order (a layer of a stack,
    or the stack; dimensions of 1 apart): a weight laid out again. A
    ``copy`` counts wherever it stands (the parent's single step holds its
    copies inside the products' fusions); a fusion INSIDE a fused
    computation writes nothing (a product that reads its layer of the stack
    through a slice fused into it)."""
    want = set()
    for shape in projections:
        dims = sorted(d for d in shape if d > 1)
        want.add(tuple(dims))
        if len(shape) == 4:                     # one layer of the stack
            layer = sorted(d for d in shape[1:] if d > 1)
            want.add(tuple(layer))
    out, fused = [], False
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            fused = line.startswith("%fused_computation")
        m = _WRITES.search(line)
        if m and m.group(2) and not (fused and m.group(3) == "fusion"):
            dims = tuple(sorted(
                d for d in map(int, m.group(2).split(",")) if d > 1))
            if dims in want:
                out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("cell,program,width,k", CASES, ids=IDS)
def test_no_program_lays_a_projection_out_again(cell, program, width, k,
                                                one_chip):
    text, projections = _compiled_text(cell, program, width, k, one_chip)
    assert projections and "tpu_custom_call" in text
    assert weight_shaped_writes(text, projections) == []


@pytest.mark.parametrize("width", [96, 16])
def test_a_decode_step_moves_the_recurrent_state_in_its_kernel_alone(
        width, one_chip):
    """ISSUE 60: the decode program updates a slot's 4 MB of float32 state
    a layer IN PLACE: one kernel call a layer beside the paged attention
    kernel's, the pool aliased input to output, and no ``copy`` or fusion
    of the pool's shape (a gather, an update and a scatter would write
    [width, 32, 256, 128] twice; a pool that is not donated through, the
    whole [97, 32, 256, 128])."""
    text, _ = _compiled_text(FALCON, "decode", width, 0, one_chip)
    assert text.count("tpu_custom_call") == 2 * 2      # two a layer
    state = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= \(?f32\[\d+,32,256,128\]\S* (copy|fusion)\(",
                          line)]
    assert state == []


# ---- the scanned dense block's decode program walks and writes (ISSUE 61) ---

# Mistral's pool at depth 2: [layers, KV heads, pages, page, head_dim]
_POOL = f"bf16[2,8,{MISTRAL['pages']},{PAGE},128]"
_POOL_WRITES = re.compile(
    r"= \(?" + re.escape(_POOL)
    + r"\S* (copy|fusion|scatter|dynamic-update-slice)\(")


@pytest.mark.parametrize("width", [32, 4])
def test_the_scanned_decode_program_writes_a_pool_in_its_kernel_alone(
        width, one_chip):
    """Mistral's layers are a ``lax.scan`` with the pools as the carry
    inside the ``while`` over a dispatch's steps: the decode call walks and
    WRITES there, the pools outputs aliased to inputs of the kernel inside
    both loops. The program compiled for a described v5e holds ONE kernel,
    whose results are the read and both pools, and no ``copy``, fusion,
    scatter or update of a pool's shape (PR 26 was such a copy: seconds a
    step)."""
    text, _ = _compiled_text(MISTRAL, "decode", width, 0, one_chip)
    kernel = [line for line in text.splitlines()
              if "tpu_custom_call" in line]
    assert len(kernel) == 1 and "paged_decode_attention" in kernel[0]
    assert kernel[0].count(_POOL) >= 4                      # in and out
    assert "output_to_operand_aliasing" in kernel[0]
    assert [line.strip()[:160] for line in text.splitlines()
            if _POOL_WRITES.search(line)] == []


@pytest.mark.parametrize("seq", [16384, 32768])
def test_the_dense_decode_program_compiles_at_a_wide_table(seq, one_chip):
    """Mistral declares 32,768 positions. Every KV head's pages of such a
    table (both halves, K and V) are 268 MB where the chip has 128 MiB of
    VMEM, which the interpreter does not have and so no other test sees:
    the walk then takes a group of KV heads a grid step (two of the eight
    at 16,384, one at 32,768: tests/test_paged_kernels.py counts the grid
    steps), and the program compiled for a described v5e still holds the
    one kernel that reads and writes both pools."""
    wide = dict(MISTRAL, name="wide", seq=seq, batch=4, pages=257,
                model=lambda: llama.LlamaConfig(
                    vocab_size=32768, dim=4096, n_layers=2, n_heads=32,
                    n_kv_heads=8, ffn_dim=14336, max_seq_len=seq,
                    rope_theta=1e6, dtype=jnp.bfloat16))
    text, _ = _compiled_text(wide, "decode", 4, 0, one_chip)
    kernel = [line for line in text.splitlines()
              if "tpu_custom_call" in line]
    assert len(kernel) == 1 and "paged_decode_attention" in kernel[0]
    assert "output_to_operand_aliasing" in kernel[0]


TINY_DENSE = dict(name="tiny", batch=4, pages=25, seq=48, chunk=16,
                  model=lambda: llama.llama_tiny(vocab_size=512))


@pytest.mark.parametrize("width", [1, 4])
def test_the_scanned_decode_program_gives_the_gather_backends_tokens(width):
    """The engine's decode program of the scanned dense block, a dispatch
    of 8 steps at widths 1 and 4 (three live slots at ragged depths, one of
    which crosses a page, and the trash row's idle lane), under the pallas
    backend (the walking body, the rows riding in) and the gather backend
    (the scatter, then the gathered view): the SAME greedy tokens, the
    lengths, and pools that agree on every page but the trash page (to the
    last ULPs: what a layer writes follows what the layer before read),
    EXACTLY the bytes they had on every page no slot wrote."""
    page, steps, b = 8, 8, TINY_DENSE["batch"]
    cfg = TINY_DENSE["model"]()
    params = block_of(cfg).init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(61)
    kv0 = kvc.init_paged_cache(cfg, TINY_DENSE["pages"], page)
    kv0 = {name: jnp.asarray(0.5 * rs.randn(*pool.shape), pool.dtype)
           for name, pool in kv0.items()}
    mp = TINY_DENSE["seq"] // page
    tables = np.zeros((b + 1, mp), np.int32)
    tables[:b] = 1 + rs.permutation(b * mp).reshape(b, mp)
    lens = np.asarray([3, 13, 30, 5, 0], np.int32)     # the trash row's: 0
    toks = jnp.asarray(rs.randint(0, 512, size=b + 1), jnp.int32)
    idx = jnp.asarray([1] if width == 1 else [0, 1, 2, b], jnp.int32)

    def run(backend):
        eng = _stand_in(TINY_DENSE, backend, page)
        fn = jax.jit(functools.partial(LLMEngine._decode_impl, eng))
        all_toks, _, kv, sl, _ = fn(
            params, dict(kv0), jnp.asarray(tables), jnp.asarray(lens), toks,
            jax.random.PRNGKey(1), jnp.zeros((b + 1,), jnp.float32), idx,
            jnp.int32(steps))
        return np.asarray(all_toks[:steps]), np.asarray(sl), kv

    got_toks, got_lens, got = run("pallas")
    want_toks, want_lens, want = run("gather")
    assert (got_toks == want_toks).all()
    assert (got_lens == want_lens).all()
    live = np.asarray(idx)[np.asarray(idx) < b]
    assert (got_lens[live] == lens[live] + steps).all()
    written = np.unique(np.concatenate([
        tables[s, lens[s] // page:(lens[s] + steps - 1) // page + 1]
        for s in live]))
    untouched = np.setdiff1d(np.arange(1, TINY_DENSE["pages"]), written)
    for name in "kv":
        a, w = np.asarray(got[name]), np.asarray(want[name])
        np.testing.assert_allclose(a[:, :, 1:], w[:, :, 1:], atol=1e-5)
        assert (a[:, :, untouched] == np.asarray(kv0[name])[
            :, :, untouched]).all()
        assert (a[:, :, written] != np.asarray(kv0[name])[
            :, :, written]).any()
        assert np.isfinite(a[:, :, 0]).all()


def test_the_dense_chunk_copies_no_more_than_it_did(one_chip):
    """Mistral's chunk of 512 rows: a slice and a copy a layer of ``wq``
    and of one of ``wk`` / ``wv``, to the layout with the contraction
    minor (``{1,0,2}`` of [H, D, hd]); from [D, H, hd] the parent made
    six such writes a layer (0e6e2ec, the same listing). A chunk is
    bound by its 512 rows' products, not by these 84 MB (PERF.md section
    7)."""
    text, projections = _compiled_text(MISTRAL, "chunk", 512, 0, one_chip)
    found = weight_shaped_writes(text, projections)
    assert len(found) <= 4, found
    assert all("{1,0,2" in line for line in found if " copy(" in line), found


def test_the_listing_finds_the_copy_of_checkpoint_layout_leaves(one_chip):
    """The negative control: the same program handed ``wq`` / ``wk`` /
    ``wv`` as a checkpoint lays them ([L, D, H, hd]) converts them on entry
    (``serve_params`` inside the program), and the listing says so; on the
    parent (read in place by ``"btd,dhk->bthk"``) it found the compiler's
    own copies under the same shapes. Under the loop of steps (ISSUE 58:
    every decode program has one, whatever its k) the conversion is a copy
    of the whole stack at entry, to the layout with the heads outermost."""
    text, projections = _compiled_text(MISTRAL, "decode", 4, 0, one_chip,
                                       served=False)
    found = weight_shaped_writes(text, projections)
    assert any("bf16[2,4096,32,128]{3,1,2,0" in line and " copy(" in line
               for line in found), found


# ---- a program's tail stands under a ``conditional`` (ISSUE 56) -------------

_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)")


def outside_conditionals(text: str) -> list:
    """The instructions of a compiled module that run whatever a
    ``conditional`` picks: ENTRY's and those of every computation reached
    from it (a fusion's, a loop's body, a call's) other than as a branch
    of a ``conditional``."""
    comps, name, entry = {}, None, None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")) and line.rstrip().endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[name] = []
            if line.startswith("ENTRY"):
                entry = name
        elif name is not None:
            comps[name].append(line)
    todo, seen, out = [entry], set(), []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            out.append(line)
            if " conditional(" not in line:
                todo += _CALLS.findall(line)
    return out


def _holds(lines, dtype: str, vocab: int) -> list:
    """The instructions that write ``dtype`` over the vocabulary."""
    shape = re.compile(rf"= \(?{dtype}\[(?:\d+,)*{vocab}[\],]")
    return [line.strip()[:160] for line in lines if shape.search(line)]


# (cell, program, width, k): one case a program kind; a dispatch that draws
# holds the sampler's random bits as u32 over rows x the vocabulary
TAIL_CASES = [(MISTRAL, "decode", 4, 0), (MISTRAL, "decode", 32, 0),
              (MISTRAL, "verify", 4, 3), (MISTRAL, "prefill", 512, 0),
              (TRINITY, "engine_chunk", 512, 0), (SDAR, "decode", 64, 0)]


@pytest.mark.parametrize("cell,program,width,k", TAIL_CASES, ids=[
    f"{cell['name']}-{prog}-w{w}-k{k}" for cell, prog, w, k in TAIL_CASES])
def test_a_programs_tail_stands_under_a_conditional(cell, program, width, k,
                                                    one_chip):
    """The sampler's random bits, in every program that samples, and in the
    engine's chunk program the head's product (float32 over the
    vocabulary) stand ONLY inside a ``conditional``: a dispatch none of
    whose rows samples draws nothing, a chunk that arms no slot reads no
    head. The control is the layers and the head as
    ``kv_cache.paged_prefill_chunk`` composes them, whose head stands
    outside."""
    text, _ = _compiled_text(cell, program, width, k, one_chip)
    vocab = cell["model"]().vocab_size
    assert _holds(text.splitlines(), "u32", vocab)      # drawn somewhere
    outside = outside_conditionals(text)
    assert " conditional(" in "\n".join(outside)
    assert _holds(outside, "u32", vocab) == []
    if program == "decode":
        # nor does a decode or block program lay its logits out again for
        # the sampler's sake whatever the branch: SDAR's [W, B, V] lies
        # B-major on the chip, and flattened before the ``cond`` it cost a
        # copy of the whole array an unmask
        assert [line for line in _holds(outside, "f32", vocab)
                if " copy(" in line or " reshape(" in line] == []
    if program == "engine_chunk":
        assert _holds(text.splitlines(), "f32", vocab)
        assert _holds(outside, "f32", vocab) == []
        always, _ = _compiled_text(cell, "chunk", width, k, one_chip)
        assert _holds(outside_conditionals(always), "f32", vocab)


# ---- on the CPU: the two forms give the same numbers ------------------------

TINY = {"llama": lambda: llama.llama_tiny(vocab_size=512),
        "afmoe": afmoe.afmoe_tiny, "sdar": sdar_moe.sdar_moe_tiny,
        "mimo": mimo.mimo_tiny}
PAGE_T, CHUNK_T = 8, 32


def _drive(cfg, params, backend: str):
    """A whole prefill into slot 0, a prompt of 48 in chunks of 32 and 16
    into slot 1, then what the block decodes by (three steps and, for the
    dense block, a verify round; a denoise pass, a pass of two blocks and a
    commit for generation by diffusion over blocks): every program's
    logits, and the pools as they stand at the end."""
    spec = block_of(cfg).cache_spec(cfg)
    full = -(-cfg.max_seq_len // PAGE_T)
    ring = kvc.ring_pages(spec.window, PAGE_T, CHUNK_T) if spec.window else 0
    kv = kvc.init_paged_cache(cfg, 2 * full + 1, PAGE_T,
                              window_pages=2 * ring + 1 if ring else 0)
    tables = jnp.asarray([list(range(1 + s * full, 1 + (s + 1) * full))
                          + list(range(1 + s * ring, 1 + (s + 1) * ring))
                          for s in range(2)], jnp.int32)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, 500,
                              jnp.int32)
    out = []
    lg, kv = kvc.paged_prefill(params, kv, tables[0], toks[:1, :32],
                               jnp.int32(20), cfg, PAGE_T)
    out.append(lg)
    for start, n in ((0, 32), (32, 16)):
        lg, kv = kvc.paged_prefill_chunk(
            params, kv, tables[1], toks[1:, start:start + n],
            jnp.int32(start), jnp.int32(48), cfg, PAGE_T, backend)
        out.append(lg)
    lens = jnp.asarray([20, 48], jnp.int32)
    if spec.block_length > 1:
        b = spec.block_length
        lg, kv, _ = kvc.paged_block_step(params, kv, tables, lens,
                                         toks[:, :b], cfg, PAGE_T, backend,
                                         commit=False)
        out.append(lg)
        lg, kv, lens, _kept = kvc.paged_block_pair_step(
            params, kv, tables, lens, toks[:, :2 * b], cfg, PAGE_T, backend)
        out.append(lg)
        _, kv, lens = kvc.paged_block_step(params, kv, tables, lens,
                                           toks[:, b:2 * b], cfg, PAGE_T,
                                           backend, commit=True)
    else:
        for i in range(3):
            lg, kv, lens = kvc.paged_decode_step(
                params, kv, tables, lens, toks[:, 50 + i], cfg, PAGE_T,
                backend)
            out.append(lg)
        if not spec.window:             # (no verify beside a ring)
            lg, kv, lens = kvc.paged_verify_step(
                params, kv, tables, lens, toks[:, 56:60], cfg, PAGE_T,
                backend)
            out.append(lg)
    return out, kv


@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("block", sorted(TINY))
def test_the_served_form_gives_the_checkpoint_forms_numbers(block, backend):
    """Every paged program TAKES either form (it converts on entry): equal
    logits and equal pools, a transposed operand of the same product."""
    cfg = TINY[block]()
    blk = block_of(cfg)
    params = blk.init_params(jax.random.PRNGKey(0), cfg)
    served = blk.serve_params(params, cfg)
    want, want_kv = _drive(cfg, params, backend)
    got, got_kv = _drive(cfg, served, backend)
    assert len(got) == len(want) >= 5
    for g, w in zip(got, want):
        assert g.shape == w.shape and bool(jnp.all(g == w))
    for name in want_kv:
        assert bool(jnp.all(got_kv[name] == want_kv[name])), name


@pytest.mark.parametrize("block", sorted(TINY))
def test_serve_params_is_idempotent_and_names_the_forms_apart(block):
    cfg = TINY[block]()
    blk = block_of(cfg)
    params = blk.init_params(jax.random.PRNGKey(0), cfg)
    served = blk.serve_params(params, cfg)
    again = blk.serve_params(served, cfg)
    assert jax.tree.structure(again) == jax.tree.structure(served)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(served)))
    layers = served["layers"]
    for was, now in zip(
            params["layers"] if isinstance(layers, list)
            else [params["layers"]],
            layers if isinstance(layers, list) else [layers]):
        moved = set(was["attn"]) - set(now["attn"])
        assert moved >= {"wq", "wk", "wv"}
        assert set(now["attn"]) - set(was["attn"]) == {
            n + HEAD_MAJOR for n in moved}
        for n in moved:
            w, hm = was["attn"][n], now["attn"][n + HEAD_MAJOR]
            assert hm.shape == w.shape[:-3] + (w.shape[-2], w.shape[-3],
                                               w.shape[-1])
            assert bool(jnp.all(jnp.swapaxes(hm, -3, -2) == w))
    # everything else is the same array, not a copy of it
    now = dict(jax.tree_util.tree_leaves_with_path(served))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if str(path[-1].key) not in ("wq", "wk", "wv", "wg"):
            assert now[path] is leaf, path


@pytest.mark.parametrize("block,leaves", [
    ("llama", {"wq_hm", "wk_hm", "wv_hm"}),
    ("afmoe", {"wq_hm", "wk_hm", "wv_hm", "wg_hm"}),
    ("sdar", {"wq_hm", "wk_hm", "wv_hm"}),
    ("mimo", {"wq_hm", "wk_hm", "wv_hm"}),
    ("lfm2", set()), ("joyai", set())])
def test_the_engine_says_which_projections_it_holds_head_major(block,
                                                               leaves):
    """``weights_head_major`` on ``engine_stats()`` / ``/v1/stats``: the
    served leaves and their bytes over the layers; empty for a block that
    serves its weights as the checkpoint lays them. The engine's tree holds
    no projection in the checkpoint's form beside its served one."""
    from ray_tpu.models import joyai, lfm2_moe
    from ray_tpu.serve.llm import LLMConfig
    make = {**TINY, "lfm2": lfm2_moe.lfm2_moe_tiny,
            "joyai": joyai.joyai_tiny}[block]
    eng = LLMEngine(LLMConfig(
        model_config=make(), max_batch_size=2, page_size=8, num_pages=64,
        max_prompt_len=64, max_seq_len=192, prefill_chunk=32,
        attention_kernel="gather", warmup_compile=False))
    try:
        got = eng.engine_stats()["weights_head_major"]
        assert set(got) == leaves
        cfg = eng.model_cfg
        if leaves:
            itemsize = jnp.dtype(cfg.dtype).itemsize
            assert got["wq_hm"] == cfg.n_layers * cfg.dim * cfg.n_heads \
                * cfg.head_dim * itemsize
            if block != "mimo":     # (its K and V differ by layer kind)
                assert got["wk_hm"] == got["wv_hm"] == cfg.n_layers \
                    * cfg.dim * cfg.n_kv_heads * cfg.head_dim * itemsize
        names = {str(path[-1].key) for path, _ in
                 jax.tree_util.tree_leaves_with_path(eng.params)
                 if hasattr(path[-1], "key")}
        assert not names & {n[:-len(HEAD_MAJOR)] for n in leaves}
    finally:
        eng.shutdown()
