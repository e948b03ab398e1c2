"""Tensor-parallel serving engine (ISSUE 20): the paged engine sharded
over a "tensor" mesh axis.

Every test here drives a REAL TP=2 mesh: conftest.py forces 8 virtual
CPU host devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)
before jax imports, so the engine's pjit/GSPMD programs and the
shard_map-wrapped pallas kernels compile genuinely partitioned.

Pins the PR's acceptance invariants:
- TP=2 greedy decode is TOKEN-IDENTICAL to TP=1 on the lossless path
  with prefix cache + speculative decoding + kv-tier restore all on,
  under both attention backends (gather/GSPMD and pallas/shard_map);
- a sharded tier store writes per-shard encoded sub-payloads under ONE
  chain digest (mode="shards" pages — the shard split lives inside the
  payload, never in the chain structure), restores reassemble
  bit-exactly, and mid-stream failover resume over a sharded chain is
  token-identical (PR 14's guarantee survives sharding);
- TP=1 and TP=2 engines index under DIFFERENT tier namespaces (the
  `|tp{N}` suffix — same precedent as `|int8`), so blob layouts never
  mix across stores;
- the engine's device state is genuinely sharded (per-KV-head pool
  split, Megatron-split weights) and the per-shard byte gauges report
  one chip's slice while page counts stay whole-replica.
"""

import time

import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve.llm import LLMConfig, LLMEngine
from ray_tpu.serve.llm.engine import kv_tier_namespace

PROMPT = "the quick brown fox jumps over the lazy dog"   # 43 byte-tokens
LONG = PROMPT + " " + PROMPT                             # 87 -> 5 full pages
REPETITIVE = "abc abc abc abc abc abc abc"               # n-gram drafts recur


def _tp_cfg(tp=2, **kw):
    # llama_tiny: n_heads=4, n_kv_heads=2, ffn_dim=128 — all divisible by
    # tp=2, and vocab 512 for the vocab-sharded lm_head. Same page/pool
    # geometry as test_kv_tier.py so the spill/restore choreography
    # (cap-2 prefix cache evicts the 3-page chain head) carries over.
    d = dict(model_config=llama.llama_tiny(vocab_size=512),
             tp_degree=tp, max_batch_size=4, page_size=16, num_pages=64,
             max_prompt_len=96, max_seq_len=160, max_tokens=8,
             prefix_cache_max_pages=2, kv_tier_enabled=True)
    d.update(kw)
    return LLMConfig(**d)


_WANT: dict = {}


def _want_tokens(prompt, max_tokens=8):
    """Greedy ground truth from a single-chip, cache-off, tier-off
    engine — the pre-TP baseline every TP run must reproduce exactly."""
    key = (prompt, max_tokens)
    if key not in _WANT:
        off = LLMEngine(_tp_cfg(tp=1, kv_tier_enabled=False,
                                prefix_cache_enabled=False), rng_seed=0)
        off.start()
        try:
            _WANT[key] = off.generate(prompt, max_tokens=max_tokens,
                                      temperature=0.0)["tokens"]
        finally:
            off.shutdown()
    return _WANT[key]


def _wait(pred, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


# ---------------------------------------------------------------------------
# sharded device state + gauges
# ---------------------------------------------------------------------------


def test_tp_engine_state_is_sharded():
    eng = LLMEngine(_tp_cfg(tp=2), rng_seed=0)
    try:
        # pool [L, Hkv, P, page, D] splits per-KV-head: each shard holds
        # Hkv/2 heads of every page
        k = eng.kv["k"]
        assert k.sharding.shard_shape(k.shape)[1] == k.shape[1] // 2
        assert k.sharding.shard_shape(k.shape)[2] == k.shape[2]
        # Megatron weight split: wq, served head-major [L, H, D, hd],
        # column-parallel on H, wo [L, H, hd, D] row-parallel, norms
        # replicated
        wq = eng.params["layers"]["attn"]["wq_hm"]
        assert wq.sharding.shard_shape(wq.shape)[1] == wq.shape[1] // 2
        wo = eng.params["layers"]["attn"]["wo"]
        assert wo.sharding.shard_shape(wo.shape)[1] == wo.shape[1] // 2
        fn = eng.params["final_norm"]
        assert fn.sharding.shard_shape(fn.shape) == fn.shape
        # small decode state rides the mesh replicated
        pt = eng._pt_dev
        assert pt.sharding.shard_shape(pt.shape) == pt.shape

        st = eng.engine_stats()
        assert st["tp_degree"] == 2
        assert st["mesh_shape"] == "tensor=2"
        pool = int(eng.kv["k"].nbytes + eng.kv["v"].nbytes)
        assert st["kv_shard_pool_bytes"] == pool // 2
        # page counts stay whole-replica: free_pages is not divided
        assert st["free_pages"] == eng.allocator.available()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("leaf,form,axis", [
    ("wq", "served", 1), ("wk", "served", 1), ("wv", "served", 1),
    ("wq", "checkpoint", 2), ("wk", "checkpoint", 2),
    ("wv", "checkpoint", 2), ("wo", "served", 1)])
def test_tp_rules_shard_a_projection_on_its_head_axis(leaf, form, axis):
    """``serve_partition_rules`` follow the served leaves (ISSUE 54):
    ``wq_hm`` / ``wk_hm`` / ``wv_hm`` [L, H, D, hd] split on H, axis 1; a
    tree as a checkpoint lays it ([L, D, H, hd], what a caller of the paged
    programs may still hand them) keeps its split on axis 2; ``wo`` [L, H,
    hd, D] is the same leaf in both forms."""
    import jax

    from ray_tpu.models.block import HEAD_MAJOR
    from ray_tpu.parallel import sharding as shd
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = llama.llama_tiny(vocab_size=512)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    if form == "served":
        params = jax.eval_shape(lambda p: llama.serve_params(p, cfg), params)
        leaf += HEAD_MAJOR if leaf != "wo" else ""
    mesh = build_mesh(MeshSpec(tensor=2), jax.devices()[:2])
    sh = shd.rule_shardings(llama.serve_partition_rules(), params, mesh)
    want = [None] * 4
    want[axis] = "tensor"
    spec = tuple(sh["layers"]["attn"][leaf].spec)   # (trailing Nones cut)
    assert spec + (None,) * (4 - len(spec)) == tuple(want)
    shape = params["layers"]["attn"][leaf].shape
    assert shape[axis] in (cfg.n_heads, cfg.n_kv_heads)
    assert sh["layers"]["attn"][leaf].shard_shape(shape)[axis] \
        == shape[axis] // 2


def test_tp1_builds_no_mesh_and_default_namespace():
    eng = LLMEngine(_tp_cfg(tp=1), rng_seed=0)
    try:
        assert eng._mesh is None and eng._tp == 1
        st = eng.engine_stats()
        assert st["tp_degree"] == 1 and st["mesh_shape"] == "none"
        assert st["kv_shard_pool_bytes"] == int(
            eng.kv["k"].nbytes + eng.kv["v"].nbytes)
    finally:
        eng.shutdown()


def test_tp_degree_must_divide_heads():
    with pytest.raises(ValueError, match="n_kv_heads"):
        LLMEngine(_tp_cfg(tp=6), rng_seed=0)


# ---------------------------------------------------------------------------
# greedy token identity: TP=2 == TP=1, full stack on, both backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_tp2_greedy_identity_full_stack(backend):
    """The PR's headline invariant: with prefix cache + spec decode +
    kv-tier restore ALL on, a TP=2 engine's greedy tokens equal the
    single-chip baseline — cold, and again through a sharded tier
    restore."""
    want = _want_tokens(LONG)
    eng = LLMEngine(_tp_cfg(tp=2, attention_kernel=backend,
                            spec_decode_enabled=True, spec_draft_len=2),
                    rng_seed=0)
    eng.start()
    try:
        assert eng.engine_stats()["attention_backend"] == backend
        cold = eng.generate(LONG, temperature=0.0)
        assert cold["error"] is None
        assert cold["tokens"] == want, "TP=2 cold decode diverged"
        # chain head evicted + spilled sharded; the rerun restores it
        assert _wait(lambda: eng.engine_stats()["spilled_pages"] >= 3)
        hot = eng.generate(LONG, temperature=0.0)["tokens"]
        assert hot == want, "TP=2 decode over sharded restore diverged"
        st = eng.engine_stats()
        assert st["restored_pages"] >= 3
        assert st["tier_hit_tokens"] >= 3 * 16
    finally:
        eng.shutdown()


def test_tp2_spec_decode_identity_and_acceptance():
    """The verify-k program under TP: drafts accepted on a repetitive
    prompt, tokens still identical to the single-chip baseline."""
    want = _want_tokens(REPETITIVE, 32)
    from ray_tpu.serve.llm.lead import IdleLead

    eng = LLMEngine(_tp_cfg(tp=2, spec_decode_enabled=True,
                            max_tokens=32), rng_seed=0)
    # the idle tier at its ceiling, where this test was written: which
    # drafts fire depends on where the pipeline's drains land (D11)
    eng._lead = IdleLead(eng._lead.tiers[-1:])
    eng.start()
    try:
        out = eng.generate(REPETITIVE, max_tokens=32, temperature=0.0)
        assert out["error"] is None
        assert out["tokens"] == want, "TP=2 speculative decode diverged"
        st = eng.engine_stats()
        assert st["spec_rounds"] > 0
        assert st["spec_drafted_tokens"] > 0
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# sharded tier blobs: per-shard payloads under one chain digest
# ---------------------------------------------------------------------------


def test_sharded_store_blob_layout_and_roundtrip():
    from ray_tpu.serve.llm.kv_cache import _chain_digest
    from ray_tpu.serve.llm.kv_tier import KVTierStore

    rng = np.random.default_rng(0)
    shape = (2, 2, 3, 4, 8)                    # [L, Hkv=2, n, page, D]
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    digest, digs = b"", []
    for i in range(3):
        digest = _chain_digest(digest, [100 + i])
        digs.append(digest.hex())
    toks = [(i + 1) * 4 for i in range(3)]

    s = KVTierStore(max_bytes=1 << 20, disk_dir=None, disk_max_bytes=0,
                    ttl_s=600.0, page_size=4, codec="lossless", shards=2)
    assert s.put(k, v, digs, toks) == 3
    # ONE blob, chain digests untouched, but each page payload carries
    # the per-shard split (mode="shards", one sub-payload per kv-head
    # shard) — the wire unit ChainStream fans to every shard
    (rec,) = s._blobs.values()
    pages = rec["data"]["pages"]
    assert len(pages) == 3
    for ek, ev in pages:
        assert ek["mode"] == "shards" and len(ek["shards"]) == 2
        assert ev["mode"] == "shards" and len(ev["shards"]) == 2
    # restore reassembles the full per-KV-head pages bit-exactly
    t, gk, gv = s.fetch_chain(digs, start=0)
    assert t == 3
    np.testing.assert_array_equal(gk, k)
    np.testing.assert_array_equal(gv, v)


def test_sharded_store_codec_none_also_shards():
    """shards>1 forces the per-page payload layout even with codec
    "none": the shard split lives inside the payload, so a raw-codec TP
    store still writes independently decodable per-shard slices."""
    from ray_tpu.serve.llm.kv_tier import KVTierStore

    rng = np.random.default_rng(1)
    k = rng.standard_normal((2, 2, 2, 4, 8)).astype(np.float32)
    v = rng.standard_normal((2, 2, 2, 4, 8)).astype(np.float32)
    digs = ["aa" * 16, "bb" * 16]
    s = KVTierStore(max_bytes=1 << 20, disk_dir=None, disk_max_bytes=0,
                    ttl_s=600.0, page_size=4, codec="none", shards=2)
    assert s.put(k, v, digs, [4, 8]) == 2
    (rec,) = s._blobs.values()
    assert "pages" in rec["data"], "sharded store must use payload layout"
    t, gk, gv = s.fetch_chain(digs, start=0)
    assert t == 2
    np.testing.assert_array_equal(gk, k)
    np.testing.assert_array_equal(gv, v)


def test_tp_engine_spills_sharded_blobs():
    eng = LLMEngine(_tp_cfg(tp=2), rng_seed=0)
    eng.start()
    try:
        want = _want_tokens(LONG)
        assert eng.generate(LONG, temperature=0.0)["tokens"] == want
        assert _wait(lambda: eng.engine_stats()["spilled_pages"] >= 3)
        blobs = list(eng._kv_tier._blobs.values())
        assert blobs
        for rec in blobs:
            for ek, ev in rec["data"]["pages"]:
                assert ek["mode"] == "shards" and len(ek["shards"]) == 2
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# namespace isolation: |tpN scopes blob layouts apart
# ---------------------------------------------------------------------------


def test_tp_namespace_isolation():
    cfg1, cfg2 = _tp_cfg(tp=1), _tp_cfg(tp=2)
    mc = cfg1.model()
    n1 = kv_tier_namespace(cfg1, mc, "float32")
    n2 = kv_tier_namespace(cfg2, mc, "float32")
    n2b = kv_tier_namespace(_tp_cfg(tp=2), mc, "float32")
    n4 = kv_tier_namespace(_tp_cfg(tp=4), mc, "float32")
    assert n1 != n2 and n2 != n4, "tp layouts must not share a namespace"
    assert n2 == n2b, "equal configs must share a namespace"
    # and the live engines inherit it, so their CP index keys never match
    a = LLMEngine(cfg1, rng_seed=0)
    b = LLMEngine(cfg2, rng_seed=0)
    try:
        assert a._kv_tier.namespace == n1
        assert b._kv_tier.namespace == n2
        assert a._kv_tier.namespace != b._kv_tier.namespace
    finally:
        a.shutdown()
        b.shutdown()


# ---------------------------------------------------------------------------
# cluster: mid-stream failover resume over a sharded chain
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tp_cluster(ray_start_module):
    yield ray_start_module


def test_failover_resume_over_sharded_chain(tp_cluster):
    """PR 14's failover guarantee through the sharded KV plane: TP=2
    engine A eagerly spills a LIVE chain as per-shard payloads, TP=2
    engine B streams it back through the CP index + object plane
    (ChainStream plans ONCE per chain — the shard split is inside each
    chunk) and resumes token-identically to the single-chip baseline."""
    want = _want_tokens(LONG, 72)
    cfg = _tp_cfg(tp=2, prefix_cache_max_pages=0, max_tokens=8)
    a = LLMEngine(cfg, rng_seed=0)
    a.start()
    b = None
    try:
        rid = a.submit(LONG, max_tokens=72, temperature=0.0)
        assert _wait(lambda: len(
            (a.request_progress(rid) or {}).get("generated") or ()) >= 12,
            timeout=120.0)
        n = a.spill_inflight()
        assert n >= 6, f"expected prompt+generated pages spilled, got {n}"
        assert _wait(lambda: a.engine_stats()["spilled_pages"] >= 6)

        b = LLMEngine(cfg, rng_seed=0)
        b.start()
        k = 12
        rid_b = b.submit(LONG, resume_tokens=want[:k],
                         max_tokens=72 - k, temperature=0.0)
        out = b.result(rid_b, timeout=180.0)
        assert out["error"] is None, out
        assert out["tokens"] == want[k:], "sharded resumed decode diverged"
        st = b.engine_stats()
        assert st["failover_resumed"] == 1
        assert st["restored_pages"] >= 6
        assert st["restore_partial"] == 0
        assert b._kv_tier.counters["remote_hits"] >= 6
    finally:
        a.shutdown()
        if b is not None:
            b.shutdown()
