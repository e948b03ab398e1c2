"""LLM serving tests (models the reference's llm serve tests:
python/ray/llm/tests/serve/ — engine correctness, OpenAI API shape,
streaming). Runs tiny-Llama on CPU."""

import json
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray_start_regular(ray_start_module):
    yield ray_start_module



def _tiny_cfg(**kw):
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMConfig

    d = dict(model_config=llama.llama_tiny(vocab_size=512),
             max_batch_size=4, page_size=16, num_pages=64,
             max_prompt_len=64, max_seq_len=128, max_tokens=8)
    d.update(kw)
    return LLMConfig(**d)


def test_paged_decode_matches_dense_forward():
    """Greedy decode through the paged KV cache must reproduce the dense
    forward pass logits step by step."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import kv_cache as kvc

    cfg = llama.llama_tiny(vocab_size=128)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    page_size = 8
    num_pages = 16
    max_pages = 4  # 32 positions

    prompt = np.array([[5, 9, 2, 7, 1]], np.int32)
    plen = prompt.shape[1]

    kv = kvc.init_paged_cache(cfg, num_pages, page_size)
    table = np.zeros((max_pages,), np.int32)
    table[:max_pages] = [3, 4, 5, 6]  # arbitrary non-contiguous pages

    logits_p, kv = kvc.paged_prefill(
        params, kv, jnp.asarray(table), jnp.asarray(prompt),
        jnp.int32(plen), cfg, page_size)

    dense = llama.forward(params, jnp.asarray(prompt), cfg)
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(dense[0, plen - 1]),
        rtol=2e-3, atol=2e-3)

    # three greedy decode steps vs dense forward over the growing sequence
    seq = list(prompt[0])
    page_tables = np.zeros((1, max_pages), np.int32)
    page_tables[0] = table
    seq_lens = jnp.asarray([plen], jnp.int32)
    tok = int(np.argmax(np.asarray(logits_p)))
    for _ in range(3):
        seq.append(tok)
        logits_d, kv, seq_lens = kvc.paged_decode_step(
            params, kv, jnp.asarray(page_tables), seq_lens,
            jnp.asarray([tok], jnp.int32), cfg, page_size)
        dense = llama.forward(params, jnp.asarray([seq], jnp.int32), cfg)
        np.testing.assert_allclose(
            np.asarray(logits_d[0]), np.asarray(dense[0, -1]),
            rtol=2e-3, atol=2e-3)
        tok = int(np.argmax(np.asarray(logits_d[0])))


def test_engine_greedy_matches_reference_loop():
    """The continuous-batching engine (greedy) must emit the same tokens as
    a naive forward-pass generation loop."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    cfg = _tiny_cfg(max_tokens=6)
    # the weights as a checkpoint lays them: what ``forward`` reads (the
    # engine keeps its own tree in the block's served form)
    params = llama.init_params(jax.random.PRNGKey(0), cfg.model())
    eng = LLMEngine(cfg, params=params)
    eng.start()
    try:
        out = eng.generate("abc")
        toks = out["tokens"]
        # reference loop on the same params
        mcfg = eng.model_cfg
        prompt = eng.tokenizer.encode("abc")
        seq = list(prompt)
        expect = []
        for _ in range(len(toks)):
            logits = llama.forward(
                params, jnp.asarray([seq], jnp.int32), mcfg)
            nxt = int(np.argmax(np.asarray(logits[0, -1])))
            expect.append(nxt)
            seq.append(nxt)
        assert toks == expect
    finally:
        eng.shutdown()


def test_chunked_prefill_matches_full_prefill():
    """paged_prefill_chunk over several chunks must build the same KV and
    final logits as one full paged_prefill (chunked prefill correctness)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import kv_cache as kvc

    cfg = llama.llama_tiny(vocab_size=128)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    page_size = 8
    num_pages = 16
    max_pages = 4

    rng = np.random.default_rng(7)
    plen = 21  # deliberately not a multiple of the chunk
    prompt = rng.integers(1, 128, size=(1, plen)).astype(np.int32)
    table = np.asarray([3, 4, 5, 6], np.int32)

    kv_full = kvc.init_paged_cache(cfg, num_pages, page_size)
    logits_full, kv_full = kvc.paged_prefill(
        params, kv_full, jnp.asarray(table), jnp.asarray(prompt),
        jnp.int32(plen), cfg, page_size)

    kv_c = kvc.init_paged_cache(cfg, num_pages, page_size)
    chunk = 8
    logits_c = None
    for start in range(0, plen, chunk):
        seg = prompt[:, start: start + chunk]
        padded = np.zeros((1, chunk), np.int32)
        padded[:, : seg.shape[1]] = seg
        logits_c, kv_c = kvc.paged_prefill_chunk(
            params, kv_c, jnp.asarray(table), jnp.asarray(padded),
            jnp.int32(start), jnp.int32(plen), cfg, page_size)

    np.testing.assert_allclose(
        np.asarray(logits_c), np.asarray(logits_full), rtol=2e-3, atol=2e-3)
    # the KV pages this slot owns must match too (pool dtype tolerance)
    for key in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(kv_c[key][:, :, table]),
            np.asarray(kv_full[key][:, :, table]), rtol=2e-3, atol=2e-3)


def test_engine_chunked_prefill_generates_same_tokens():
    """An engine forced into chunked prefill (tiny prefill_chunk) must emit
    exactly the tokens the unchunked engine emits (greedy)."""
    from ray_tpu.serve.llm import LLMEngine

    prompt = "the quick brown fox jumps over the lazy dog"  # 43 byte-tokens
    ref_cfg = _tiny_cfg(max_tokens=6, prefill_chunk=512)
    ref_eng = LLMEngine(ref_cfg, rng_seed=0)
    ref_eng.start()
    try:
        expect = ref_eng.generate(prompt)["tokens"]
    finally:
        ref_eng.shutdown()

    cfg = _tiny_cfg(max_tokens=6, prefill_chunk=16)
    eng = LLMEngine(cfg, rng_seed=0)
    eng.start()
    try:
        # a concurrent short request exercises the decode/chunk interleave
        rid_long = eng.submit(prompt)
        rid_short = eng.submit("abc")
        out_long = eng.result(rid_long, timeout=120.0)
        out_short = eng.result(rid_short, timeout=120.0)
        assert out_long["error"] is None and out_short["error"] is None
        assert out_long["tokens"] == expect
        assert eng.stats["prefills"] >= 2
    finally:
        eng.shutdown()


def test_engine_concurrent_and_paging():
    from ray_tpu.serve.llm import LLMEngine

    cfg = _tiny_cfg(max_batch_size=2, num_pages=32, max_tokens=5)
    eng = LLMEngine(cfg)
    eng.start()
    try:
        ids = [eng.submit(f"req {i}") for i in range(5)]
        outs = [eng.result(r, timeout=120.0) for r in ids]
        assert all(o["error"] is None for o in outs)
        assert all(o["num_generated_tokens"] == 5 for o in outs)
        stats = eng.engine_stats()
        assert stats["active_slots"] == 0
        assert stats["free_pages"] == 31  # all pages recycled (page 0 trash)
    finally:
        eng.shutdown()


@pytest.fixture
def llm_app(ray_start_regular):
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_openai_app

    app = build_openai_app(_tiny_cfg(), route_prefix="/v1")
    serve.run(app, name="llm", route_prefix="/v1")
    proxy = serve.start_http_proxy(port=0)
    base = f"http://127.0.0.1:{proxy.port}"
    yield base
    serve.shutdown()


def _post(url, payload, timeout=120.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def test_openai_http_completions(llm_app):
    status, body = _post(f"{llm_app}/v1/completions",
                         {"prompt": "hello", "max_tokens": 4})
    assert status == 200
    out = json.loads(body)
    assert out["object"] == "text_completion"
    assert out["usage"]["completion_tokens"] == 4
    assert isinstance(out["choices"][0]["text"], str)

    status, body = _post(f"{llm_app}/v1/chat/completions",
                         {"messages": [{"role": "user", "content": "hi"}],
                          "max_tokens": 3})
    out = json.loads(body)
    assert out["choices"][0]["message"]["role"] == "assistant"

    with urllib.request.urlopen(f"{llm_app}/v1/models", timeout=30) as r:
        models = json.loads(r.read())
    assert models["data"][0]["id"] == "llama-tiny"


def test_replica_reports_its_start_up_ledger(llm_app):
    """Through serve.run: the replica's worker stamps ``worker_boot`` from
    its process's creation and ``actor_wait`` up to the deployment's
    constructor, then the engine's stages; ``/v1/stats`` carries them."""
    from ray_tpu.observability import profiling

    with urllib.request.urlopen(f"{llm_app}/v1/stats", timeout=60) as r:
        stats = json.loads(r.read())
    su = stats["startup"]
    stages = su["stages"]
    names = [n for n, _s, _d in stages]
    assert names[:2] == ["worker_boot", "actor_wait"]
    assert names[-1] == "ready" and set(names) == set(profiling.STARTUP_STAGES)
    # created precedes every stamp; the first stage starts AT it
    assert stages[0][1] == su["created"] and stages[0][2] > 0
    end = su["created"]
    for _n, start, seconds in stages:
        assert start >= end - 1e-6 and seconds >= 0
        end = start + seconds
    assert su["ready"] == stages[-1][1]
    assert stats["startup_s"] == pytest.approx(su["ready"] - su["created"],
                                               abs=2e-3)
    # the replica's clock and this process's are the machine's: ready lies
    # behind us, the worker's creation after this test process's
    assert profiling.startup().created < su["created"] < su["ready"] \
        < time.monotonic()
    name, is_main = su["built_on"]
    assert isinstance(name, str) and is_main is False
    assert {tuple(p["sig"]) for p in su["programs"]} >= {
        ("split_key",), ("patch", "state"), ("patch", "toks")}
    # warmed on the thread that built the engine, never on the loop's
    assert all(p["thread"] != "llm-engine" for p in su["programs"]
               if not p["mid_traffic"])
    for key in profiling.STARTUP_TOTALS:
        assert key in stats


def test_openai_http_streaming(llm_app):
    status, body = _post(
        f"{llm_app}/v1/completions",
        {"prompt": "stream", "max_tokens": 5, "stream": True})
    assert status == 200
    lines = [ln for ln in body.decode().split("\n\n") if ln.startswith("data: ")]
    assert lines[-1] == "data: [DONE]"
    chunks = [json.loads(ln[len("data: "):]) for ln in lines[:-1]]
    assert chunks, "no SSE chunks"
    text = "".join(c["choices"][0]["text"] for c in chunks)
    finishes = [c["choices"][0]["finish_reason"] for c in chunks]
    assert finishes[-1] == "stop"
    assert isinstance(text, str)


def test_slot_reuse_no_kv_corruption():
    """A freed slot's device page table must be invalidated: otherwise later
    decode blocks keep scattering its junk KV into pages reallocated to a
    NEW request, corrupting its completion. Greedy output of a request must
    not depend on an earlier request having used (and freed) its pages."""
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.llm.engine import LLMEngine

    def make_engine():
        cfg = LLMConfig(
            model_id="t", model_config=llama.llama_tiny(vocab_size=512),
            max_batch_size=2, page_size=16, num_pages=24,
            max_prompt_len=64, max_seq_len=128, max_tokens=24,
            decode_block=4)
        eng = LLMEngine(cfg, rng_seed=7)
        eng.start()
        return eng

    probe = [5, 9, 2] * 8

    eng = make_engine()
    clean = eng.generate(probe, max_tokens=16, temperature=0.0)["tokens"]
    eng.shutdown()

    eng = make_engine()
    # short request grabs slot 0 + pages, finishes fast, slot is freed
    # mid-pipeline while the longer one still decodes
    a = eng.submit([1] * 4, max_tokens=2, temperature=0.0)
    b = eng.submit([2] * 30, max_tokens=20, temperature=0.0)
    eng.result(a, timeout=60)
    eng.result(b, timeout=60)
    # new request reuses the freed slot/pages; its greedy output must match
    # the clean-engine run exactly
    out = eng.generate(probe, max_tokens=16, temperature=0.0)["tokens"]
    eng.shutdown()
    assert out == clean


def test_engine_loads_checkpoint(tmp_path):
    """checkpoint_path round-trip: an engine built from saved params emits
    the same greedy tokens as one holding them in memory (the serving analog
    of weight loading; reference: vLLM model loading)."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.llm.engine import LLMEngine

    mc = llama.llama_tiny(vocab_size=512)
    params = llama.init_params(jax.random.PRNGKey(42), mc)
    path = llama.save_params(params, str(tmp_path / "ckpt"))
    assert path.endswith("params.npz")

    base = dict(model_id="t", model_config=mc, max_batch_size=2,
                page_size=16, num_pages=24, max_prompt_len=64,
                max_seq_len=128, max_tokens=16)
    e1 = LLMEngine(LLMConfig(**base), params=params)
    e1.start()
    want = e1.generate([3, 1, 4] * 6, max_tokens=8, temperature=0.0)["tokens"]
    e1.shutdown()

    e2 = LLMEngine(LLMConfig(**base, checkpoint_path=str(tmp_path / "ckpt")))
    e2.start()
    got = e2.generate([3, 1, 4] * 6, max_tokens=8, temperature=0.0)["tokens"]
    e2.shutdown()
    assert got == want

    # config mismatch fails loudly
    import pytest as _pytest
    with _pytest.raises(ValueError, match="does not match"):
        llama.load_params(str(tmp_path / "ckpt"),
                          llama.llama_tiny(vocab_size=300))


def test_cancel_waiting_request_releases_result_waiter():
    """cancel() on a still-WAITING request must set done_event: a result()
    waiter already parked on it would otherwise block for its full
    timeout even though the request is gone."""
    import threading

    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(_tiny_cfg(), rng_seed=0)
    # engine loop deliberately NOT started: the request stays WAITING
    rid = eng.submit("abc")
    out = {}
    waiter = threading.Thread(
        target=lambda: out.update(eng.result(rid, timeout=60)))
    waiter.start()
    time.sleep(0.2)  # let the waiter park on done_event
    t0 = time.monotonic()
    eng.cancel(rid)
    waiter.join(timeout=10)
    assert not waiter.is_alive(), "result() still blocked after cancel()"
    assert time.monotonic() - t0 < 5.0
    assert out["tokens"] == [] and out["error"] is None
    # cancel removed all tracking state (nothing will ever drain it)
    assert eng.drain(rid)["error"] == "unknown request"


def test_engine_sheds_expired_waiting_request():
    """The admission loop drops WAITING requests whose deadline passed —
    no slot, no pages, no prefill — and the result() waiter gets a fast
    'deadline exceeded' error instead of its full timeout."""
    from ray_tpu.core import deadline as request_deadline
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(_tiny_cfg(), rng_seed=0)
    # engine loop deliberately NOT started: the request stays WAITING
    with request_deadline.scope(time.time() + 0.1):
        rid = eng.submit("abc")
    assert eng._requests[rid].deadline is not None  # captured at submit
    time.sleep(0.15)
    eng._shed_expired_waiting()  # what _admit() runs first each pass
    out = eng.result(rid, timeout=5)
    assert out["error"] == "deadline exceeded"
    assert out["tokens"] == []
    assert eng.stats["shed_expired"] == 1

    # a live deadline rides along without shedding
    with request_deadline.scope(time.time() + 60.0):
        rid2 = eng.submit("abc")
    eng._shed_expired_waiting()
    assert len(eng._waiting) == 1  # still queued, not shed
    eng.cancel(rid2)


def test_decode_block_tier_selection():
    """_select_block's three tiers: admissions blocked (waiting + free
    slots, or a chunked prefill mid-flight) -> 1; slot-starved (waiting,
    no free slots) -> pressure_decode_block; idle -> what keeps the
    device fed: one step, then the pressure tier's k, and decode_block
    (its ceiling) only once the loop has seen the device run dry with
    less (ISSUE 42; tests/test_idle_lead.py holds the rule)."""
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.llm.lead import CLIMB_DRY

    def climb(eng):
        for _ in range(CLIMB_DRY):
            eng._lead.observe(1, eng._collector.pause_n)

    eng = LLMEngine(_tiny_cfg(decode_block=8, pressure_decode_block=2),
                    rng_seed=0)
    assert eng._select_block() == 1          # idle: the smallest that feeds
    eng._waiting = [object()]
    assert eng._select_block() == 1          # waiting + free slots
    eng.free_slots = []
    assert eng._select_block() == 2          # slot-starved: pressure tier
    eng._waiting = []
    eng._prefilling = [object()]
    assert eng._select_block() == 1          # chunked prefill mid-flight
    eng._prefilling = []
    assert eng._select_block() == 1          # back to idle
    climb(eng)
    assert eng._select_block() == 2          # the host fell behind
    climb(eng)
    assert eng._select_block() == 8          # and again: the ceiling
    eng._waiting = [object()]
    assert eng._select_block() == 2          # the pressure tier keeps its k

    # pressure tier clamps to decode_block (a misconfigured larger value
    # must not out-dispatch the idle tier)
    big = LLMEngine(_tiny_cfg(decode_block=4, pressure_decode_block=16),
                    rng_seed=0)
    big._waiting = [object()]
    big.free_slots = []
    assert big._select_block() == 4
    big._waiting = []
    climb(big)
    assert big._select_block() == 4

    # spec decode caps the idle tier at spec_draft_len (draft probing
    # happens between blocks; see _select_block docstring)
    spec = LLMEngine(_tiny_cfg(decode_block=8, spec_decode_enabled=True,
                               spec_draft_len=4), rng_seed=0)
    assert spec._select_block() == 1
    climb(spec)
    climb(spec)
    assert spec._select_block() == 4


def test_bucket_width_padding():
    """_bucket_width packs active slots into power-of-two widths with a
    floor of 4, capped at max_batch_size."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(_tiny_cfg(max_batch_size=16, num_pages=96), rng_seed=0)
    assert eng._bucket_width(1) == 4    # floor
    assert eng._bucket_width(4) == 4
    assert eng._bucket_width(5) == 8
    assert eng._bucket_width(9) == 16
    assert eng._bucket_width(16) == 16  # cap == max_batch_size

    small = LLMEngine(_tiny_cfg(max_batch_size=3), rng_seed=0)
    assert small._bucket_width(2) == 3  # cap below the floor
    assert small._bucket_width(3) == 3


def test_engine_loop_exception_fails_stranded_requests_and_health():
    """The loop thread has no other handler: an exception out of an
    iteration must fail the requests it strands (not leave them to their
    timeouts), refuse new ones, reach the log, and flip the replica's
    health check."""
    import time

    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.llm.llm_server import LLMServer

    eng = LLMEngine(_tiny_cfg(max_tokens=16), rng_seed=0)
    srv = LLMServer.__new__(LLMServer)      # the server around THIS engine
    srv.cfg, srv.engine = eng.cfg, eng
    eng.start()
    try:
        assert eng.generate("healthy", max_tokens=4)["error"] is None
        assert srv.check_health() is True

        def boom():
            raise RuntimeError("injected device fault")

        eng._decode_step = boom
        rid = eng.submit("stranded", max_tokens=16)
        t0 = time.monotonic()
        out = eng.result(rid, timeout=60.0)
        assert time.monotonic() - t0 < 30.0     # failed, not timed out
        assert "engine loop failed" in out["error"]
        assert "injected device fault" in out["error"]
        assert "injected device fault" in eng.loop_error
        with pytest.raises(RuntimeError, match="engine loop failed"):
            eng.submit("after the fault")
        with pytest.raises(RuntimeError, match="engine loop failed"):
            srv.check_health()
    finally:
        eng.shutdown()
