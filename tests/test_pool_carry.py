"""The KV pool as an in-place loop carry (ISSUE 26).

Inside every paged program the pool ``{"k", "v"}`` of shape
``[n_layers, Hkv, P, page, D]`` is a loop CARRY that is only ever updated
by a scatter; the layer is a dynamic index into it. Pinned here:

- structure: no ``scan`` / ``while`` of a paged program has a pool-shaped
  or layer-of-the-pool-shaped ``xs`` / ``ys`` / closed-over constant, and
  no equation produces such a value except the scatter (and the loops and
  calls that pass the carry through) — what keeps the next edit from
  putting a ``pool[l]`` back;
- kernels: the 5-D pool + layer call returns bit for bit what the 4-D
  call returns on ``pool[l]``;
- contents: after one decode step, one verify step, one whole prefill and
  one chunk on a pool pre-filled with a known pattern, every element
  outside the written ``(l, :, page_idx, offset)`` positions is unchanged,
  the trash page and a prefix page shared by two slots included.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.ops import paged_attention as paged_ops
from ray_tpu.serve.llm import kv_cache

PAGE, N_PAGES, MAX_PAGES = 8, 20, 4
CFG = llama.llama_tiny(vocab_size=128, n_layers=3)
POOL_SHAPE = (CFG.n_layers, CFG.n_kv_heads, N_PAGES, PAGE, CFG.head_dim)
# two slots share prefix page 3; the third row is an idle slot on the
# trash page
PAGE_TABLES = jnp.asarray([[3, 5, 7, 0], [3, 6, 8, 9], [0, 0, 0, 0]],
                          jnp.int32)
SEQ_LENS = jnp.asarray([9, 17, 0], jnp.int32)
TOKENS = jnp.asarray([5, 9, 1], jnp.int32)
PROGRAMS = ("decode", "verify", "prefill", "chunk")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(1), CFG)


def _patterned_pool():
    """A pool no program's k/v can reproduce by accident."""
    return {name: 100.0 + jax.random.normal(
        jax.random.PRNGKey(i), POOL_SHAPE, CFG.dtype)
        for i, name in enumerate(("k", "v"))}


def _program(name, params, backend):
    """(fn(kv) -> outputs with new_kv at [1], written [(page, offset)])."""
    pos = np.asarray(SEQ_LENS)
    pts = np.asarray(PAGE_TABLES)
    if name == "decode":
        def fn(kv):
            return kv_cache.paged_decode_step(
                params, kv, PAGE_TABLES, SEQ_LENS, TOKENS, CFG, PAGE,
                backend)
        written = [(pts[b, p // PAGE], p % PAGE) for b, p in enumerate(pos)]
    elif name == "verify":
        t = 3
        def fn(kv):
            return kv_cache.paged_verify_step(
                params, kv, PAGE_TABLES, SEQ_LENS,
                jnp.stack([TOKENS + i for i in range(t)], axis=1), CFG,
                PAGE, backend)
        written = [(pts[b, (p + i) // PAGE], (p + i) % PAGE)
                   for b, p in enumerate(pos) for i in range(t)]
    elif name == "prefill":
        # a whole prefill starts at position 0: its slot shares no prefix
        bucket, true_len, own = 16, 11, [10, 11, 12, 0]
        def fn(kv):
            return kv_cache.paged_prefill(
                params, kv, jnp.asarray(own, jnp.int32),
                jnp.arange(bucket)[None] % 100, jnp.int32(true_len), CFG,
                PAGE)
        # padding positions (>= true_len) land in the trash page
        written = [(own[p // PAGE] if p < true_len else 0, p % PAGE)
                   for p in range(bucket)]
    else:
        clen, start, true_len = 16, 8, 21
        def fn(kv):
            return kv_cache.paged_prefill_chunk(
                params, kv, PAGE_TABLES[1], jnp.arange(clen)[None] % 100,
                jnp.int32(start), jnp.int32(true_len), CFG, PAGE, backend)
        written = [(pts[1, p // PAGE] if p < true_len else 0, p % PAGE)
                   for p in range(start, start + clen)]
    return fn, written


# ---------------------------------------------------------------------------
# structure: the pool is a carry, never xs / ys / a slice
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _equations(jaxpr):
    """Every equation of the program, loop and call bodies included; a
    Pallas kernel's own body (block-shaped refs) is not the program's."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in _sub_jaxprs(eqn):
                yield from _equations(sub)


# equations that may RETURN a pool-shaped value: the in-place update, and
# the loops / calls the carry passes through
_MAY_RETURN_POOL = {"scatter", "scan", "while", "pjit", "jit", "closed_call",
                    "core_call", "custom_jvp_call", "custom_vjp_call",
                    "remat", "checkpoint"}


def _assert_pool_is_carry_only(jaxpr, n_loops_expected):
    layer_shapes = {POOL_SHAPE[1:], (1,) + POOL_SHAPE[1:]}

    def shape(v):
        return tuple(getattr(v.aval, "shape", ()))

    loops = 0
    for eqn in _equations(jaxpr):
        prim = eqn.primitive.name
        for out in eqn.outvars:
            assert shape(out) not in layer_shapes, \
                f"{prim} makes one layer of the pool: {eqn}"
            if shape(out) == POOL_SHAPE and prim == "pallas_call":
                # the kernel that walks a slot's pages writes the call's
                # rows where they lie (ISSUE 53; the decode and verify
                # calls since ISSUE 61): a pool is its result only as
                # the ALIAS of the pool it was handed
                aliased = dict(eqn.params["input_output_aliases"])
                at = eqn.outvars.index(out)
                assert at in aliased.values(), \
                    f"a kernel makes a pool it was not handed: {eqn}"
                assert all(shape(eqn.invars[i]) == POOL_SHAPE
                           for i, o in aliased.items() if o == at)
            elif shape(out) == POOL_SHAPE:
                assert prim in _MAY_RETURN_POOL, \
                    f"{prim} makes a pool-shaped value: {eqn}"
        if prim == "scan":
            n_consts = eqn.params["num_consts"]
            n_carry = eqn.params["num_carry"]
            carries = eqn.invars[n_consts:n_consts + n_carry]
            if not any(shape(v) == POOL_SHAPE for v in eqn.invars):
                continue
            loops += 1
            assert sum(shape(v) == POOL_SHAPE for v in carries) == 2
            not_carry = list(eqn.invars[:n_consts]) \
                + list(eqn.invars[n_consts + n_carry:]) \
                + list(eqn.outvars[n_carry:])
            for v in not_carry:
                assert shape(v) != POOL_SHAPE and \
                    shape(v) not in layer_shapes, \
                    f"pool-shaped const / xs / ys of a scan: {v.aval}"
        elif prim == "while":
            n_consts = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
            for v in eqn.invars[:n_consts]:
                assert shape(v) != POOL_SHAPE
            loops += any(shape(v) == POOL_SHAPE for v in eqn.invars)
    assert loops == n_loops_expected


@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("name", PROGRAMS)
def test_pool_is_only_a_loop_carry(params, name, backend):
    fn, _ = _program(name, params, backend)
    kv = kv_cache.init_paged_cache(CFG, N_PAGES, PAGE)
    assert kv["k"].shape == POOL_SHAPE
    _assert_pool_is_carry_only(jax.make_jaxpr(fn)(kv).jaxpr, 1)


def test_pool_is_only_a_loop_carry_in_the_engine_decode_block():
    """The engine's fused block: the loop over steps (a ``while`` whose
    bound is the program's operand, ISSUE 58) around the scan over layers
    carries the same pool (so the donated argument can alias)."""
    from ray_tpu.serve.llm import LLMConfig, LLMEngine

    cfg = LLMConfig(model_config=CFG, max_batch_size=2, page_size=PAGE,
                    num_pages=N_PAGES, max_prompt_len=16, max_seq_len=32,
                    max_tokens=4, attention_kernel="pallas")
    eng = LLMEngine(cfg, rng_seed=0)
    try:
        def block(kv):
            return eng._decode_impl(
                eng.params, kv, eng._pt_dev, eng._sl_dev,
                jnp.zeros((3,), jnp.int32), jax.random.PRNGKey(0),
                eng._temps_dev, jnp.arange(2, dtype=jnp.int32),
                jnp.int32(4))

        _assert_pool_is_carry_only(jax.make_jaxpr(block)(eng.kv).jaxpr, 2)
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# kernels: 5-D pool + layer == 4-D call on pool[l], bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("kind", ["decode", "verify", "chunk"])
def test_kernel_on_layer_indexed_pool_equals_kernel_on_the_layer(kind, layer):
    n_layers, hkv, n_rep, d, page, mp, b = 3, 2, 2, 16, 8, 4, 2
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(7), 3)
    k_pool = jax.random.normal(
        kk, (n_layers, hkv, mp * b + 1, page, d), jnp.float32)
    v_pool = jax.random.normal(kv_, k_pool.shape, jnp.float32)
    page_tables = jnp.arange(1, mp * b + 1, dtype=jnp.int32).reshape(b, mp)
    lens = jnp.asarray([5, 17], jnp.int32)
    l = jnp.int32(layer)
    if kind == "decode":
        q = jax.random.normal(kq, (b, hkv * n_rep, d), jnp.float32)
        call, rest = paged_ops.paged_decode_attention, (page_tables, lens)
    elif kind == "verify":
        q = jax.random.normal(kq, (b, 3, hkv * n_rep, d), jnp.float32)
        call, rest = paged_ops.paged_verify_attention, (page_tables, lens)
    else:
        q = jax.random.normal(kq, (1, 16, hkv * n_rep, d), jnp.float32)
        call = paged_ops.paged_chunk_attention
        rest = (page_tables[1], jnp.int32(8), jnp.int32(21))
    # the layer index is traced, as in the programs' scan over layers
    got = jax.jit(lambda l: call(q, k_pool, v_pool, *rest, l))(l)
    want = call(q, k_pool[layer], v_pool[layer], *rest)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    other = call(q, k_pool[1], v_pool[1], *rest)
    assert not np.array_equal(np.asarray(got), np.asarray(other))


# ---------------------------------------------------------------------------
# contents: only the written (l, :, page, offset) positions change
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("name", PROGRAMS)
def test_program_changes_only_the_positions_it_writes(params, name, backend):
    fn, written = _program(name, params, backend)
    before = _patterned_pool()
    after = jax.jit(fn)(before)[1]
    mask = np.zeros(POOL_SHAPE, bool)
    for page, offset in written:
        mask[:, :, page, offset] = True
    for which in ("k", "v"):
        old = np.asarray(before[which])
        new = np.asarray(after[which])
        assert new.shape == POOL_SHAPE and new.dtype == old.dtype
        np.testing.assert_array_equal(new[~mask], old[~mask])
        # every written row holds the model's k/v, not the pattern: real
        # k/v are nowhere near 100
        assert np.all(np.abs(new[mask]) < 50.0)
    # the prefix page two slots share is never written; the trash page only
    # by idle slots and padding positions
    shared = int(PAGE_TABLES[0, 0])
    assert not mask[:, :, shared].any()
    trash_offsets = sorted({o for p, o in written if p == 0})
    assert not mask[:, :, 0, [o for o in range(PAGE)
                              if o not in trash_offsets]].any()


# ---------------------------------------------------------------------------
# page operations: the engine, disaggregation and the tier move pages through
# kv_cache's functions, and nothing else under ray_tpu/ knows the pool's format
# ---------------------------------------------------------------------------

# slot 1's pages of PAGE_TABLES: the shared prefix page first
OWN_PAGES = [3, 6, 8, 9]


def _assert_pools_equal(got, want):
    for which in ("k", "v"):
        assert got[which].dtype == want[which].dtype
        np.testing.assert_array_equal(np.asarray(got[which]),
                                      np.asarray(want[which]))


def test_gather_then_scatter_returns_the_pool_bit_for_bit():
    before = _patterned_pool()
    bk, bv = kv_cache.gather_pages(before, OWN_PAGES)
    assert bk.shape == POOL_SHAPE[:2] + (len(OWN_PAGES),) + POOL_SHAPE[3:]
    k_np, v_np = kv_cache.fetch_pages(bk, bv, 3)
    np.testing.assert_array_equal(
        k_np, np.asarray(before["k"])[:, :, OWN_PAGES[:3]])
    np.testing.assert_array_equal(
        v_np, np.asarray(before["v"])[:, :, OWN_PAGES[:3]])
    # wipe the pages, then put the gathered blob back
    idx = jnp.asarray(OWN_PAGES, jnp.int32)
    wiped = kv_cache.scatter_pages(
        before, *kv_cache.zero_pages(before, len(OWN_PAGES)), idx)
    assert not np.asarray(wiped["k"])[:, :, OWN_PAGES].any()
    _assert_pools_equal(kv_cache.scatter_pages(wiped, bk, bv, idx), before)


def test_scatter_padded_with_the_trash_page_changes_no_real_page():
    before = _patterned_pool()
    t = 2
    pairs = [tuple(np.full(POOL_SHAPE[:2] + (1,) + POOL_SHAPE[3:], 7.0 + i,
                           np.asarray(before["k"]).dtype)
                   for _ in "kv") for i in range(t)]
    bk, bv = kv_cache.pack_pages(pairs, MAX_PAGES)
    tgt = np.zeros((MAX_PAGES,), np.int32)
    tgt[:t] = OWN_PAGES[1:1 + t]
    after = jax.jit(kv_cache.scatter_pages)(before, bk, bv, tgt)
    untouched = [p for p in range(1, N_PAGES) if p not in tgt[:t]]
    for which in ("k", "v"):
        old, new = np.asarray(before[which]), np.asarray(after[which])
        np.testing.assert_array_equal(new[:, :, untouched],
                                      old[:, :, untouched])
        for i in range(t):
            assert (new[:, :, tgt[i]] == 7.0 + i).all()


@pytest.mark.parametrize("sizes,width", [((1,), 4), ((1, 1, 1), 4),
                                         ((1, 1, 1, 1), 4), ((3,), 4),
                                         ((2, 1), 8)])
def test_pack_pages_equals_concat_and_pad(sizes, width):
    rng = np.random.default_rng(0)
    pairs = [tuple(rng.standard_normal(
        POOL_SHAPE[:2] + (n,) + POOL_SHAPE[3:]).astype(np.float32)
        for _ in "kv") for n in sizes]
    bk, bv = kv_cache.pack_pages(pairs, width)
    # what the engine's restore paths and disagg's adoption each wrote out
    t = sum(sizes)
    for got, col in ((bk, 0), (bv, 1)):
        cat = np.concatenate([p[col] for p in pairs], axis=2)
        pad = np.zeros(cat.shape[:2] + (width - t,) + cat.shape[3:],
                       cat.dtype)
        np.testing.assert_array_equal(
            got, np.concatenate([cat, pad], axis=2))
        np.testing.assert_array_equal(got, np.pad(
            cat, ((0, 0), (0, 0), (0, width - t), (0, 0), (0, 0))))
        assert got.dtype == cat.dtype and got.shape[2] == width


def test_pool_keeps_its_sharding_through_the_shared_inject_program():
    """A 2-device "tensor" mesh (conftest forces 8 virtual CPU devices): the
    ONE donated inject program the tier's restore, the warm start and
    disagg's adoption share returns the pool split per KV head as it got
    it, with the host pages in place."""
    from ray_tpu.serve.llm import LLMConfig, LLMEngine

    cfg = LLMConfig(model_config=CFG, tp_degree=2, max_batch_size=2,
                    page_size=PAGE, num_pages=N_PAGES, max_prompt_len=16,
                    max_seq_len=PAGE * MAX_PAGES, max_tokens=4)
    eng = LLMEngine(cfg, rng_seed=0)
    try:
        sharding = eng.kv["k"].sharding
        assert sharding.shard_shape(POOL_SHAPE)[1] == POOL_SHAPE[1] // 2
        assert sharding.spec == kv_cache.pool_spec()
        blob = np.arange(np.prod(POOL_SHAPE[:2] + (2,) + POOL_SHAPE[3:]),
                         dtype=np.float32).reshape(
            POOL_SHAPE[:2] + (2,) + POOL_SHAPE[3:])
        eng._inject_host_pages([(blob[:, :, :1], -blob[:, :, :1]),
                                (blob[:, :, 1:], -blob[:, :, 1:])], [5, 9])
        for which, want in (("k", blob), ("v", -blob)):
            assert eng.kv[which].sharding == sharding
            got = np.asarray(eng.kv[which])
            np.testing.assert_array_equal(got[:, :, [5, 9]], want)
            assert not got[:, :, [p for p in range(1, N_PAGES)
                                  if p not in (5, 9)]].any()
        assert kv_cache.pool_nbytes(eng.kv) == 2 * blob.nbytes // 2 * N_PAGES
        # one fixed shape: another count of pages compiles nothing new
        compiled = eng._inject_kv._cache_size()
        eng._inject_host_pages([(blob[:, :, :1], blob[:, :, :1])], [7])
        assert eng._inject_kv._cache_size() == compiled
    finally:
        eng.shutdown()


def test_only_kv_cache_subscripts_the_device_pool():
    """What keeps the next edit from writing the pool's layout down in a
    second module: under ray_tpu/ only kv_cache.py may take ``kv["k"]`` /
    ``kv["v"]`` or index ``<engine>.kv[...]``. (The kernels of
    ops/paged_attention.py take the two arrays by layout from kv_cache.py,
    their only caller; host blobs have their own names.)"""
    import pathlib
    import re

    import ray_tpu

    pat = re.compile(r"""\bkv\[\s*["'][kv]["']\s*\]|\.kv\[""")
    root = pathlib.Path(ray_tpu.__file__).parent
    offenders = []
    for path in sorted(root.rglob("*.py")):
        if path.relative_to(root).as_posix() == "serve/llm/kv_cache.py":
            continue
        for n, line in enumerate(path.read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(f"{path.relative_to(root)}:{n}: "
                                 f"{line.strip()}")
    assert not offenders, "\n".join(offenders)
    # the scan sees what it is meant to see
    assert pat.search('bk = jnp.take(self.kv["k"], pidx, axis=2)')
    assert pat.search("x = eng.kv['v'][:, :, pidx]")
