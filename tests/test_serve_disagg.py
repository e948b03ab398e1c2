"""Prefill/decode disaggregation tests (reference:
python/ray/llm/_internal/serve/deployments/prefill_decode_disagg/
prefill_decode_disagg.py + its serve tests). Tiny-Llama on CPU."""

import json
import urllib.request

import numpy as np
import pytest

import ray_tpu


def _tiny_cfg(**kw):
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMConfig

    d = dict(model_config=llama.llama_tiny(vocab_size=512),
             max_batch_size=4, page_size=16, num_pages=64,
             max_prompt_len=64, max_seq_len=128, max_tokens=8)
    d.update(kw)
    return LLMConfig(**d)


def test_prefill_handoff_matches_monolithic():
    """A prompt prefilled on engine A and decoded on engine B must emit the
    same greedy tokens as one engine doing both — the KV pages really carry
    the prompt state across the handoff."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm.disagg import DecodeEngine, prefill_only
    from ray_tpu.serve.llm.engine import LLMEngine

    cfg = _tiny_cfg(max_tokens=6)
    mc = cfg.model()
    params = llama.init_params(jax.random.PRNGKey(3), mc)

    mono = LLMEngine(cfg, params=params)
    mono.start()
    want = mono.generate([7, 3, 9, 1, 4] * 4, max_tokens=6,
                         temperature=0.0)["tokens"]
    mono.shutdown()

    pre = LLMEngine(cfg, params=params)       # prefill role: loop NOT started
    dec = DecodeEngine(cfg, params=params)    # decode role
    dec.start()
    try:
        state = prefill_only(pre, [7, 3, 9, 1, 4] * 4, temperature=0.0)
        assert state["plen"] == 20
        assert state["kv_k"].shape[2] == state["n_pages"]
        rid = dec.submit_prefilled(state, max_tokens=6)
        got = dec.result(rid, timeout=120.0)
        assert got["error"] is None
        assert got["tokens"] == want
        # pages recycled on both sides
        assert pre.engine_stats()["free_pages"] == cfg.num_pages - 1
    finally:
        dec.shutdown()


def test_disagg_decode_concurrency_and_page_recycling():
    """Several prefilled requests stream through one decode engine; slots
    and pages fully recycle."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm.disagg import DecodeEngine, prefill_only
    from ray_tpu.serve.llm.engine import LLMEngine

    cfg = _tiny_cfg(max_batch_size=2, num_pages=32, max_tokens=5)
    mc = cfg.model()
    params = llama.init_params(jax.random.PRNGKey(5), mc)
    pre = LLMEngine(cfg, params=params)
    dec = DecodeEngine(cfg, params=params)
    dec.start()
    try:
        rids = []
        for i in range(5):
            state = prefill_only(pre, [i + 1] * 8, temperature=0.0)
            rids.append(dec.submit_prefilled(state, max_tokens=5))
        outs = [dec.result(r, timeout=120.0) for r in rids]
        assert all(o["error"] is None for o in outs)
        assert all(o["num_generated_tokens"] == 5 for o in outs)
        stats = dec.engine_stats()
        assert stats["active_slots"] == 0
        assert stats["free_pages"] == 31
    finally:
        dec.shutdown()


@pytest.fixture
def disagg_app(ray_start_module):
    from ray_tpu import serve
    from ray_tpu.serve.llm.disagg import build_disagg_openai_app

    app = build_disagg_openai_app(_tiny_cfg(), route_prefix="/v1",
                                  num_prefill=2, num_decode=1)
    serve.run(app, name="llm-disagg", route_prefix="/v1")
    proxy = serve.start_http_proxy(port=0)
    yield f"http://127.0.0.1:{proxy.port}"
    serve.shutdown()


def test_disagg_openai_http_e2e(disagg_app):
    """End-to-end: distinct prefill replicas and a decode ingress serving
    OpenAI requests over HTTP (VERDICT r2 item 4's done-bar)."""
    def post(payload):
        req = urllib.request.Request(
            f"{disagg_app}/v1/completions",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    outs = [post({"prompt": f"hello {i}", "max_tokens": 4,
                  "temperature": 0.0}) for i in range(4)]
    for out in outs:
        assert out["object"] == "text_completion"
        assert out["usage"]["completion_tokens"] == 4
        assert out["ray_tpu"]["ttft_s"] is not None

    # chat route must NOT fall through to the plain completions path
    req = urllib.request.Request(
        f"{disagg_app}/v1/chat/completions",
        data=json.dumps({"messages": [{"role": "user", "content": "hi"}],
                         "max_tokens": 3}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        chat = json.loads(r.read())
    assert chat["choices"][0]["message"]["role"] == "assistant"

    with urllib.request.urlopen(f"{disagg_app}/v1/models", timeout=30) as r:
        models = json.loads(r.read())
    assert models["data"][0]["mode"] == "disagg"


@pytest.fixture
def disagg_dag_app(ray_start_module):
    from ray_tpu import serve
    from ray_tpu.serve.llm.disagg import build_disagg_openai_app

    app = build_disagg_openai_app(_tiny_cfg(), route_prefix="/v1",
                                  num_prefill=2, num_decode=1,
                                  use_pipeline=True)
    serve.run(app, name="llm-disagg-dag", route_prefix="/v1")
    proxy = serve.start_http_proxy(port=0)
    yield f"http://{'127.0.0.1'}:{proxy.port}"
    serve.shutdown()


def test_disagg_dag_pipeline_e2e(disagg_dag_app):
    """The prefill→decode handoff re-expressed on the compiled pipeline
    (mutable-channel aDAG path, VERDICT r3 item 4): same OpenAI surface,
    KV blobs ride channel edges instead of object-plane task returns."""
    def post(payload):
        req = urllib.request.Request(
            f"{disagg_dag_app}/v1/completions",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    outs = [post({"prompt": f"hello {i}", "max_tokens": 4,
                  "temperature": 0.0}) for i in range(4)]
    for out in outs:
        assert out["object"] == "text_completion"
        assert out["usage"]["completion_tokens"] == 4


def test_handoff_channel_capacity_sizing():
    """ADVICE r4: the compiled-pipeline channel must fit the LARGEST KV
    handoff blob the config can produce (>1 page, model dtype), not the
    8 MiB default that only fit the tiny test config."""
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.serve.llm.config import LLMConfig
    from ray_tpu.serve.llm.disagg import _handoff_channel_capacity

    mc = llama.llama3_1b(max_seq_len=2048)
    cfg = LLMConfig(model_id="x", model_config=mc, page_size=128,
                    max_prompt_len=1024, max_seq_len=2048)
    cap = _handoff_channel_capacity(cfg)
    pages = -(-cfg.max_prompt_len // cfg.page_size)
    assert pages == 8  # a real multi-page prompt
    kv_bytes = 2 * mc.n_layers * mc.n_kv_heads * pages * cfg.page_size \
        * mc.head_dim * np.dtype(mc.dtype).itemsize
    assert cap > kv_bytes          # blob + framing headroom fits
    assert cap > 8 * 1024 * 1024   # and exceeds the old default
    # picklable envelope of that worst-case blob actually fits
    import pickle
    blob = {"kv_k": np.zeros((mc.n_layers, mc.n_kv_heads, pages,
                              cfg.page_size, mc.head_dim),
                             np.dtype(mc.dtype)),
            "kv_v": np.zeros((mc.n_layers, mc.n_kv_heads, pages,
                              cfg.page_size, mc.head_dim),
                             np.dtype(mc.dtype)),
            "prompt_tokens": list(range(cfg.max_prompt_len))}
    assert len(pickle.dumps(blob, protocol=5)) <= cap


def test_handoff_capacity_encoded_sizing():
    """ISSUE 16 satellite: with a wire codec on, the channel is sized
    from the MEASURED raw/encoded ratio — trusting only half of it and
    never dropping below raw sizing (an unmeasured or degenerate probe
    must stay raw-safe; overflow poisons the pipe, headroom is cheap)."""
    from ray_tpu.models import llama
    from ray_tpu.serve.llm.config import LLMConfig
    from ray_tpu.serve.llm.disagg import _handoff_channel_capacity

    mc = llama.llama3_1b(max_seq_len=2048)

    def cap(**kw):
        cfg = LLMConfig(model_id="x", model_config=mc, page_size=128,
                        max_prompt_len=1024, max_seq_len=2048, **kw)
        return _handoff_channel_capacity(
            cfg, measured_ratio=kw.pop("_ratio", None))

    raw = cap(disagg_wire_codec="none")
    # lossless wire, no probe -> raw-safe (ratio floors at 1.0)
    assert cap(disagg_wire_codec="lossless") == raw
    # measured 6x compression -> capacity shrinks, but only by ratio/2
    pages = -(-1024 // 128)
    kv_bytes = 2 * mc.n_layers * mc.n_kv_heads * pages * 128 \
        * mc.head_dim * np.dtype(mc.dtype).itemsize
    shrunk = _handoff_channel_capacity(
        LLMConfig(model_id="x", model_config=mc, page_size=128,
                  max_prompt_len=1024, max_seq_len=2048),
        measured_ratio=6.0)
    assert shrunk < raw
    assert shrunk >= int((kv_bytes / 3.0) * 1.25)  # half of 6x trusted
    # degenerate probe (ratio < 2: half would EXPAND) floors to raw
    assert _handoff_channel_capacity(
        LLMConfig(model_id="x", model_config=mc, page_size=128,
                  max_prompt_len=1024, max_seq_len=2048),
        measured_ratio=0.8) == raw


# ---------------------------------------------------------------------------
# fleet disaggregation on the streamed KV plane (ISSUE 16)
# ---------------------------------------------------------------------------

def _fleet_cfg(**kw):
    """Tier-enabled config shared by the prefill and decode sides — the
    shared kv_tier_namespace over it is what makes prefill registrations
    restorable on decode engines."""
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMConfig

    d = dict(model_config=llama.llama_tiny(vocab_size=512),
             max_batch_size=4, page_size=16, num_pages=64,
             max_prompt_len=96, max_seq_len=160, max_tokens=8,
             prefix_cache_enabled=True, kv_tier_enabled=True)
    d.update(kw)
    return LLMConfig(**d)


def _want_tokens(prompt, cfg=None, max_tokens=8):
    """Greedy ground truth from a cache-off, tier-off engine (same seed
    = same random-init weights as every fleet engine)."""
    from ray_tpu.serve.llm import LLMEngine

    base = cfg or _fleet_cfg()
    import dataclasses
    off = LLMEngine(dataclasses.replace(base, kv_tier_enabled=False,
                                        prefix_cache_enabled=False),
                    rng_seed=0)
    off.start()
    try:
        return off.generate(prompt, max_tokens=max_tokens,
                            temperature=0.0)["tokens"]
    finally:
        off.shutdown()


def test_wire_codec_roundtrip_lossless_and_none():
    """The disagg wire blob must decode bit-exactly under `lossless` and
    pass through untouched under `none` (mixed-codec rollouts: the decode
    side accepts both shapes)."""
    from ray_tpu.serve.llm.disagg import _decode_state, _encode_state

    rng = np.random.default_rng(0)
    kv_k = rng.standard_normal((2, 2, 3, 16, 8)).astype(np.float32)
    kv_v = rng.standard_normal((2, 2, 3, 16, 8)).astype(np.float32)
    state = {"prompt_tokens": [1] * 40, "plen": 40, "n_pages": 3,
             "first_token": 7, "kv_k": kv_k, "kv_v": kv_v,
             "temperature": 0.0, "prefill_ttft_s": 0.01}

    enc = _encode_state(dict(state), "lossless")
    assert "kv_k" not in enc and len(enc["enc_pages"]) == 3
    assert enc["wire_bytes"] > 0
    assert enc["first_token"] == 7  # metadata rides along
    dec = _decode_state(enc)
    np.testing.assert_array_equal(dec["kv_k"], kv_k)
    np.testing.assert_array_equal(dec["kv_v"], kv_v)

    # `none` passes through; raw blobs pass decode untouched
    assert _encode_state(state, "none") is state
    assert _decode_state(state) is state

    # int8: bounded per-(layer,head) quantization error, 4x smaller wire
    enc8 = _encode_state(dict(state), "int8")
    dec8 = _decode_state(enc8)
    bound = max(np.abs(kv_k).max(), np.abs(kv_v).max()) / 127.0 * 1.01
    assert np.abs(dec8["kv_k"] - kv_k).max() <= bound
    assert np.abs(dec8["kv_v"] - kv_v).max() <= bound
    assert enc8["wire_bytes"] < enc["wire_bytes"]


def test_int8_divergence_policy_gate():
    """The quality policy gating int8 on the disagg wire: measured
    greedy divergence against the deployment bound; the default bound
    demands bit-identity so int8 never silently defaults on."""
    from ray_tpu.serve.llm.disagg import (int8_wire_allowed,
                                          int8_wire_divergence)

    assert int8_wire_divergence([1, 2, 3], [1, 2, 3]) == 0.0
    assert int8_wire_divergence([1, 2, 3, 4], [1, 2, 9, 4]) == 0.25
    # length mismatch counts every unmatched position
    assert int8_wire_divergence([1, 2], [1, 2, 5, 6]) == 0.5
    assert int8_wire_divergence([], []) == 0.0

    cfg = _tiny_cfg()
    assert cfg.disagg_int8_max_divergence == 0.0
    assert int8_wire_allowed(cfg, 0.0)
    assert not int8_wire_allowed(cfg, 1e-6)
    loose = _tiny_cfg(disagg_int8_max_divergence=0.05)
    assert int8_wire_allowed(loose, 0.04)
    assert not int8_wire_allowed(loose, 0.06)


def test_prompt_tokens_for_http():
    """Proxy-side prompt sizing for the disagg threshold: mirrors the
    engine's tokenization + max_prompt_len cap; non-LLM routes and
    failures answer 0 (which never crosses a positive threshold)."""
    from ray_tpu.serve import affinity

    from ray_tpu.serve.llm.tokenizer import get_tokenizer

    meta = {"tokenizer": "byte", "page_size": 16, "max_prompt_len": 32}
    assert affinity.prompt_tokens_for_http(
        "/completions", {"prompt": "hello"}, meta) == len(
            get_tokenizer("byte").encode("hello"))
    # capped at the deployment's max_prompt_len, like the engine
    assert affinity.prompt_tokens_for_http(
        "/completions", {"prompt": "x" * 80}, meta) == 32
    chat = {"messages": [{"role": "user", "content": "hi"}]}
    assert affinity.prompt_tokens_for_http(
        "/chat/completions", chat, meta) > 0
    assert affinity.prompt_tokens_for_http("/models", {}, meta) == 0
    assert affinity.prompt_tokens_for_http(
        "/completions", {"prompt": "x"}, {}) == 0  # broken meta degrades


class _AID:
    def __init__(self, h):
        self._h = h

    def hex(self):
        return self._h


class _Rep:
    def __init__(self, name):
        self._actor_id = _AID(name)


def test_router_disagg_plan_threshold_routing():
    """Router.disagg_plan unit contract: the third placement mode fires
    only for deployments advertising a prefill pool, only past the
    threshold, and discounts what the decode pool already holds."""
    import threading

    from ray_tpu.serve.config import RouterConfig
    from ray_tpu.serve.router import ReplicaSet, Router

    rs = ReplicaSet(RouterConfig(), "llm")
    rs.update([_Rep("r0"), _Rep("r1")], 0)
    digs = [f"{i:02x}" * 16 for i in range(6)]
    meta = {"tokenizer": "byte", "page_size": 16, "max_prompt_len": 96,
            "disagg_prefill": "llm-prefill", "disagg_prompt_threshold": 32}
    rs.apply_summaries(1, meta, {"r0": digs[:4]})
    rs.summaries_ok_at = __import__("time").monotonic()

    rtr = Router.__new__(Router)  # disagg_plan touches only _lock/_sets
    rtr._lock = threading.Lock()
    rtr._sets = {"llm": rs}

    # under threshold -> colocated
    assert rtr.disagg_plan("llm", None, 20) is None
    assert rtr.disagg_plan("llm", None, 32) is None  # exactly at: colocated
    # long cold prompt -> prefill pool, full estimate
    plan = rtr.disagg_plan("llm", ["ff" * 16], 90)
    assert plan == {"prefill_deployment": "llm-prefill",
                    "est_prefill_tokens": 90}
    # hot prefix discounts below threshold -> colocated (the handoff only
    # pays for COLD prefill FLOPs)
    assert rtr.disagg_plan("llm", digs[:5], 90) is None  # 90 - 4*16 = 26
    # unknown deployment / no meta / zero prompt -> colocated
    assert rtr.disagg_plan("nope", None, 500) is None
    assert rtr.disagg_plan("llm", None, 0) is None
    plain = ReplicaSet(RouterConfig(), "plain")
    plain.update([_Rep("p0")], 0)
    rtr._sets["plain"] = plain
    assert rtr.disagg_plan("plain", None, 500) is None
    # threshold 0 disables the mode entirely
    rs.apply_summaries(2, dict(meta, disagg_prompt_threshold=0),
                       {"r0": digs[:4]})
    assert rtr.disagg_plan("llm", None, 500) is None
    # stale summaries: no discount evidence -> assume cold, still plan
    rs.apply_summaries(3, meta, {"r0": digs[:4]})
    rs.summaries_ok_at = 0.0
    plan = rtr.disagg_plan("llm", digs[:5], 90)
    assert plan is not None and plan["est_prefill_tokens"] == 90


def test_tier_flush_index_barrier():
    """flush_index drains the ordered publisher queue: once it returns
    True every earlier put is registered (the handshake that lets the
    proxy dispatch the decode leg right after prefill_stream returns)."""
    from ray_tpu.serve.llm.kv_tier import KVTierStore

    store = KVTierStore(max_bytes=1 << 20, disk_dir=None, disk_max_bytes=0,
                        ttl_s=60.0, page_size=16)
    try:
        assert store.flush_index(2.0) is True  # empty queue: immediate
        k = np.zeros((1, 1, 2, 16, 4), np.float32)
        assert store.put(k, k, digests=["aa" * 16, "bb" * 16],
                         tokens=[16, 32]) == 2
        assert store.flush_index(2.0) is True  # drains behind the puts
    finally:
        store.close()


# ---- cluster: streamed handoff over the CP index (keep LAST: the
# module-scoped runtime stays up once started) ------------------------------

FLEET_PROMPT = "the quick brown fox jumps over the lazy dog " * 2  # 88 toks


def test_streamed_handoff_token_identity(ray_start_module):
    """Tentpole contract: a prompt prefilled via prefill_stream (KV
    spilled through the tier codec + CP index) and decoded by a plain
    tier-enabled engine emits the SAME greedy tokens as one engine doing
    both — and the decode engine's restore accounting lands in the
    disagg counters."""
    from ray_tpu.serve.llm.disagg import PrefillServer
    from ray_tpu.serve.llm.engine import LLMEngine

    cfg = _fleet_cfg()
    prompt = FLEET_PROMPT + "alpha"
    want = _want_tokens(prompt)

    from ray_tpu.serve.llm.tokenizer import get_tokenizer
    ntoks = len(get_tokenizer(cfg.tokenizer).encode(prompt))
    pre = PrefillServer(cfg)
    desc = pre.prefill_stream("/completions", {"prompt": prompt})
    assert desc["plen"] == ntoks
    assert desc["pages_registered"] == ntoks // cfg.page_size
    assert desc["wire_bytes"] > 0
    assert desc["prefill_ttft_s"] > 0

    dec = LLMEngine(cfg, rng_seed=0)
    dec.start()
    try:
        out = dec.generate(prompt, temperature=0.0, disagg=True)
        assert out["error"] is None
        assert out["tokens"] == want
        st = dec.engine_stats()
        assert st["disagg_prefills"] == 1
        assert st["handoff_bytes_wire"] > 0
        assert st["restored_pages"] >= 1
        # prefill-side wire accounting mirrors the handoff
        assert pre.engine_stats()["handoff_bytes_wire"] >= desc["wire_bytes"]
        assert pre.engine_stats()["mode"] == "prefill"
    finally:
        dec.shutdown()


def test_dead_prefill_degrades_to_partial_restore(ray_start_module):
    """Satellite: a prefill replica dying mid-stream (chunk fault seam)
    degrades the decode side to a PARTIAL restore + tail prefill — the
    request still completes greedy-identical, restore_partial is
    counted, and the partial flag rides the restore stage attrs (what
    the proxy's breaker charge keys on)."""
    from ray_tpu.serve.llm.disagg import PrefillServer
    from ray_tpu.serve.llm.engine import LLMEngine

    cfg = _fleet_cfg(kv_tier_chunk_pages=2)
    prompt = FLEET_PROMPT + "bravo"
    want = _want_tokens(prompt, cfg=cfg)

    from ray_tpu.serve.llm.tokenizer import get_tokenizer
    ntoks = len(get_tokenizer(cfg.tokenizer).encode(prompt))
    pre = PrefillServer(cfg)
    desc = pre.prefill_stream("/completions", {"prompt": prompt})
    assert desc["pages_registered"] == ntoks // cfg.page_size

    dec = LLMEngine(cfg, rng_seed=0)
    dec.start()

    def fault(chunk_idx):
        if chunk_idx >= 1:  # first chunk lands, then the owner "dies"
            raise RuntimeError("prefill replica died mid-stream")

    dec._kv_tier._chunk_fault = fault
    try:
        out = dec.generate(prompt, temperature=0.0, disagg=True)
        assert out["error"] is None
        assert out["tokens"] == want  # tail prefill recomputed the rest
        st = dec.engine_stats()
        assert st["restore_partial"] >= 1
        assert st["disagg_prefills"] == 1
        assert 1 <= st["restored_pages"] < desc["pages_registered"]
        restore = [s for s in out["stages"] if s["stage"] == "restore"]
        assert restore and restore[-1]["attrs"]["partial"] is True
    finally:
        dec.shutdown()


@pytest.fixture
def fleet_app(ray_start_module):
    from ray_tpu import serve
    from ray_tpu.serve.llm.disagg import build_disagg_fleet_app

    cfg = _fleet_cfg(disagg_prompt_threshold=32)
    app = build_disagg_fleet_app(cfg, route_prefix="/v1",
                                 num_prefill=1, num_decode=1)
    serve.run(app, name="llm-fleet", route_prefix="/v1")
    proxy = serve.start_http_proxy(port=0)
    yield f"http://127.0.0.1:{proxy.port}", cfg
    serve.shutdown()


@pytest.mark.slow
def test_fleet_disagg_http_e2e(fleet_app):
    """End-to-end fleet disagg: long prompts route through the prefill
    pool (router plan -> prefill_stream -> streamed restore on the
    decode ingress), the proxy/engine disagg counters move, roles show
    in controller status, and the served completion is greedy-identical
    to a monolithic engine."""
    import time as _time

    base, cfg = fleet_app

    def post(payload):
        req = urllib.request.Request(
            f"{base}/v1/completions", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    def proxy_stats():
        with urllib.request.urlopen(f"{base}/-/stats", timeout=30) as r:
            return json.loads(r.read())

    # each attempt uses a FRESH long prompt: a served prompt's prefix
    # goes resident on the decode replica, and the plan's discount then
    # (correctly) keeps repeats colocated — only cold prompts disagg
    deadline = _time.monotonic() + 180
    hit_prompt, hit_out, i = None, None, 0
    while _time.monotonic() < deadline and hit_prompt is None:
        prompt = f"req{i:03d} " + FLEET_PROMPT
        out = post({"prompt": prompt, "max_tokens": 6, "temperature": 0.0})
        assert out["usage"]["completion_tokens"] == 6
        if proxy_stats()["disagg_prefills"] >= 1:
            hit_prompt, hit_out = prompt, out
        i += 1
        _time.sleep(0.5)
    assert hit_prompt is not None, \
        "no request took the disagg path within the deadline"

    # greedy identity across the whole disagg path
    want = _want_tokens(hit_prompt, cfg=cfg, max_tokens=6)
    from ray_tpu.serve.llm.tokenizer import get_tokenizer
    assert hit_out["choices"][0]["text"] == get_tokenizer(
        cfg.tokenizer).decode(want)

    # roles + engine counters through the controller
    import ray_tpu as _rt
    from ray_tpu.serve.controller import get_or_create_controller
    rows = _rt.get(get_or_create_controller().detailed_status.remote(),
                   timeout=30.0)
    fleet = {k: v for k, v in rows.items() if v.get("app") == "llm-fleet"}
    assert {"prefill", "decode"} <= {v.get("role") for v in fleet.values()}
    decode_engines = [e for v in fleet.values()
                      if v.get("role") == "decode"
                      for e in (v.get("engine") or []) if e]
    assert decode_engines
    assert any(e.get("disagg_prefills", 0) >= 1 for e in decode_engines)
    assert any(e.get("handoff_bytes_wire", 0) > 0 for e in decode_engines)
    assert all(e.get("handoff_overlap_ms", 0.0) >= 0.0
               for e in decode_engines)
