"""chip_smoke.py: does the system still start on the chip?

Drives the two hot paths once through the entry points users call, at the
full width of ``llama.llama3_1b()`` (dim 2048, 16 layers, 16/8 heads of
128, ffn 8192, vocab 128256), with random weights from a seed:

- *kernels*: in a child process that holds the chip, the paged-attention
  family (decode, verify T=5, chunk C=512) and flash attention forward +
  backward, COMPILED, each against its float32 reference under a stated
  tolerance;
- *train*: ``JaxTrainer.fit()`` on a TPU worker, the benchmark's train
  recipe (flash attention, dots remat, adafactor, fsdp over the chips it
  is given, 4 x 2048), five steps on one repeated batch: finite, falling
  loss;
- *serve*: ``ray_tpu.init()`` -> ``serve.run(build_openai_app(cfg))`` ->
  ``serve.start_http_proxy``, then ``/v1/completions`` over HTTP: plain and
  SSE requests, eight concurrent streams, a chunked-prefill prompt and a
  prefix hit; ``/v1/stats`` must say pallas, compiled, on a TPU.

This process never initialises a JAX backend: one process holds the chip at
a time, and each holder is gone before the next starts. Any phase failing
ends the run with a non-zero exit code, no ``"ok": true`` line, and the tail
of the chip-holding worker's stderr. Without a TPU (``JAX_PLATFORMS=cpu``, or no
chip device nodes) it exits non-zero at once: there is no CPU mode.
``--cpu-rehearsal`` is a debugging aid that walks the same control flow at
toy sizes on the CPU and says ``"platform": "cpu"``; it is never what the
default does and its numbers are not device numbers.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # TP=4 serving, fsdp=4 training

Standard output ends with two JSON lines. The second to last is the report
(``{"report": {...versions, per-phase compile/run times, kernel errors,
mid_traffic_compiles, object store..., "claim": null}}``). The LAST line is
the result and holds exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
with the device as jax reported it in the chip-holding processes. A failed
phase on a found device ends with ``{"ok": false, "device": {...}}`` instead
and a non-zero exit code.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import glob
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150.0          # the contract allows 1200 s, compilation included

# max |kernel - float32 reference| <= TOL * max(1, max |reference|), on
# unit-variance inputs. bf16 carries 8 mantissa bits: logits, probabilities
# and outputs are each rounded once (2^-9 relative each) on values of order
# 1 — a few 1e-2 at worst.
TOL = 5e-2


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def sizes(rehearsal: bool, chips: int) -> dict:
    """On the chip: a one-chip serving configuration, nothing cut (a cold
    run takes about five of the twenty minutes allowed). Toy sizes for the
    CPU rehearsal."""
    if rehearsal:
        return dict(
            kern=dict(hkv=2, h=4, d=16, page=8, max_pages=4, pool=40, b=4,
                      chunk=16, flash=(2, 64, 4, 4, 16),
                      flash_gqa=(2, 64, 4, 2, 16), heads64=(2, 4),
                      experts=(16, 4, 32, 16)),
            train=dict(batch=4, seq=64, steps=5),
            serve=dict(max_batch_size=8, page_size=8, num_pages=160,
                       max_prompt_len=96, max_seq_len=128, prefill_chunk=32,
                       max_tokens=8, attention_kernel="pallas",
                       tp_degree=chips,
                       ray_actor_options={"resources": {"TPU": chips}}),
            prompt_tokens=8, long_tokens=70, shared_tokens=48)
    return dict(
        kern=dict(hkv=8, h=16, d=128, page=128, max_pages=16, pool=288, b=32,
                  chunk=512, flash=(4, 2048, 16, 16, 128),
                  flash_gqa=(2, 2048, 32, 8, 128), heads64=(8, 32),
                  experts=(256, 32, 2048, 1792)),
        train=dict(batch=4, seq=2048, steps=5),
        serve=dict(max_batch_size=32, page_size=128, num_pages=288,
                   max_prompt_len=1024, max_seq_len=2048,
                   decode_block=8, pipeline_depth=3, pressure_decode_block=2,
                   max_tokens=16, tp_degree=chips,
                   ray_actor_options={"resources": {"TPU": chips}}),
        prompt_tokens=128, long_tokens=900, shared_tokens=768)


# ---------------------------------------------------------------------------
# phase 1: kernels (runs in a child process: `--phase kernels`)
# ---------------------------------------------------------------------------

def serve_model(rehearsal: bool, **kw):
    """The served model: llama3_1b at full width, or the rehearsal's toy
    (8/4 heads so that it splits four ways)."""
    from ray_tpu.models import llama
    if rehearsal:
        return llama.llama_tiny(vocab_size=512, n_heads=8, n_kv_heads=4,
                                **kw)
    return llama.llama3_1b(max_seq_len=2048, **kw)


def tp_decode_collectives(rehearsal: bool, chips: int) -> dict:
    """Collective ops in the engine's own compiled decode program at
    tp_degree=chips (depth cut to 2: the program is a scan over layers, so
    depth adds no op kinds): the proof that TP serving is partitioned
    rather than replicated."""
    import jax.numpy as jnp

    from __graft_entry__ import collective_counts
    from ray_tpu.serve.llm import LLMConfig, LLMEngine

    sv = sizes(rehearsal, chips)["serve"]
    eng = LLMEngine(LLMConfig(model_config=serve_model(rehearsal, n_layers=2),
                              warmup_compile=False,
                              **{k: v for k, v in sv.items()
                                 if k != "ray_actor_options"}))
    idx = jnp.full((4,), eng.cfg.max_batch_size, jnp.int32)
    toks = jnp.zeros((eng.cfg.max_batch_size + 1,), jnp.int32)
    hlo = eng._decode.lower(
        eng.params, eng.kv, eng._pt_dev, eng._sl_dev, toks, eng._rng,
        eng._temps_dev, idx, 1).compile().as_text()
    return collective_counts(hlo)


def kernels_child(rehearsal: bool, chips: int) -> int:
    """Holds the chip. Prints one JSON line: device identity, versions and
    per-kernel compile/run times and max-abs errors."""
    import jax
    import jax.numpy as jnp
    import jaxlib
    import numpy as np

    from ray_tpu.ops import attention as flash_ops
    from ray_tpu.ops import paged_attention as paged_ops

    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())},
           "jax": jax.__version__, "jaxlib": jaxlib.__version__,
           "libtpu": None, "interpret": paged_ops.interpret_default(),
           "kernels": {}}
    try:
        import libtpu
        out["libtpu"] = libtpu.__version__
    except ImportError:
        pass
    if not rehearsal and (dev.platform != "tpu" or out["interpret"]):
        print(f"chip_smoke kernels: jax found platform={dev.platform!r}, "
              f"not a TPU", file=sys.stderr)
        return 3
    if dev.memory_stats() is None and dev.platform == "tpu":
        print("chip_smoke kernels: the TPU reports no memory_stats()",
              file=sys.stderr)
        return 3

    k = sizes(rehearsal, 1)["kern"]
    dt = jnp.float32 if rehearsal else jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    kp = jax.random.normal(keys[0], (k["hkv"], k["pool"], k["page"], k["d"]),
                           dt)
    vp = jax.random.normal(keys[1], kp.shape, dt)
    max_len = k["max_pages"] * k["page"]
    hi = jax.lax.Precision.HIGHEST

    def ref_paged(q, page_tables, base, limit, kp=kp, vp=vp):
        """The gather path's formula (kv_cache._attend's decode read) in
        float32 throughout; kp / vp [Hkv, P, page, D]."""
        q, kf, vf = (x.astype(jnp.float32) for x in (q, kp, vp))
        b, t, h, d = q.shape
        hkv = kf.shape[0]
        n_rep = h // hkv
        sm = d ** -0.5
        ks = jnp.moveaxis(jnp.take(kf, page_tables, axis=1), 0, 3).reshape(
            b, max_len, hkv, d)
        vs = jnp.moveaxis(jnp.take(vf, page_tables, axis=1), 0, 3).reshape(
            b, max_len, hkv, d)
        ks, vs = (jnp.repeat(x, n_rep, axis=2) for x in (ks, vs))
        col = jnp.arange(max_len)
        pos = base[:, None] + jnp.arange(t)[None, :]
        valid = (col[None, None] <= pos[:, :, None]) \
            & (col[None, None] < limit[:, None, None])
        s = jnp.einsum("bqhd,bkhd->bhqk", q, ks, precision=hi) * sm
        s = jnp.where(valid[:, None], s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vs,
                          precision=hi)

    def timed(name, fn, ref):
        """First call compiles and runs, second runs: their difference is
        the compile share. block_until_ready is the fence."""
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn())
        t1 = time.perf_counter()
        got = jax.block_until_ready(fn())
        t2 = time.perf_counter()
        want = jax.tree.leaves(ref())
        err = max(float(jnp.max(jnp.abs(g.astype(jnp.float32) - r)))
                  for g, r in zip(jax.tree.leaves(got), want))
        ref_max = max(float(jnp.max(jnp.abs(r))) for r in want)
        finite = all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
                     for g in jax.tree.leaves(got))
        out["kernels"][name] = {
            "compile_s": round(max(0.0, (t1 - t0) - (t2 - t1)), 3),
            "run_s": round(t2 - t1, 4), "max_abs_err": err,
            "ref_max": ref_max, "tol": TOL, "finite": finite}
        return finite and err <= TOL * max(1.0, ref_max)

    b = k["b"]
    rng = np.random.default_rng(0)
    # scattered pool pages (the kernels only read) and ragged live lengths
    pt = jnp.asarray(rng.integers(1, k["pool"], (b, k["max_pages"])),
                     jnp.int32)
    pos = jnp.asarray(rng.integers(1, max_len - 8, (b,)), jnp.int32)
    lim = jnp.full((b,), max_len, jnp.int32)
    q1 = jax.random.normal(keys[2], (b, k["h"], k["d"]), dt)
    q5 = jax.random.normal(keys[3], (b, 5, k["h"], k["d"]), dt)
    qc = jax.random.normal(keys[4], (1, k["chunk"], k["h"], k["d"]), dt)
    start = jnp.int32(3 * k["page"])
    true_len = jnp.int32(3 * k["page"] + k["chunk"] - 3)

    decode = jax.jit(paged_ops.paged_decode_attention)
    verify = jax.jit(paged_ops.paged_verify_attention)
    chunk = jax.jit(paged_ops.paged_chunk_attention)
    ok = timed("paged_decode", lambda: decode(q1, kp, vp, pt, pos),
               lambda: ref_paged(q1[:, None], pt, pos, lim)[:, 0])
    ok &= timed("paged_verify_t5", lambda: verify(q5, kp, vp, pt, pos),
                lambda: ref_paged(q5, pt, pos, lim))
    ok &= timed("paged_chunk", lambda: chunk(qc, kp, vp, pt[0], start,
                                             true_len),
                lambda: ref_paged(qc, pt[:1], start[None], true_len[None]))

    # heads of 64: the pool holds two KV heads side by side in a 128-lane
    # row (kv_cache.pool_heads_lanes); the same three kernels, same names
    hkv64, h64 = k["heads64"]
    k64 = jax.random.normal(keys[5], (hkv64, k["pool"], k["page"], 64), dt)
    v64 = jax.random.normal(keys[6], k64.shape, dt)

    def packed(x):          # [Hkv, P, page, 64] -> [Hkv / 2, P, page, 128]
        return jnp.moveaxis(x.reshape(hkv64 // 2, 2, *x.shape[1:]), 1,
                            3).reshape(hkv64 // 2, k["pool"], k["page"], 128)

    kp64, vp64 = packed(k64), packed(v64)
    q1, q5, qc = (jax.random.normal(keys[7], x.shape[:-2] + (h64, 64), dt)
                  for x in (q1, q5, qc))
    ok &= timed("paged_decode_h64", lambda: decode(q1, kp64, vp64, pt, pos),
                lambda: ref_paged(q1[:, None], pt, pos, lim, k64, v64)[:, 0])
    ok &= timed("paged_verify_t5_h64",
                lambda: verify(q5, kp64, vp64, pt, pos),
                lambda: ref_paged(q5, pt, pos, lim, k64, v64))
    ok &= timed("paged_chunk_h64",
                lambda: chunk(qc, kp64, vp64, pt[0], start, true_len),
                lambda: ref_paged(qc, pt[:1], start[None], true_len[None],
                                  k64, v64))

    # the grouped expert product (rows sorted by expert, one group a held
    # expert) against its plain form: every row through its own expert
    from ray_tpu.ops import grouped_matmul as grouped_ops
    from ray_tpu.parallel import expert as expert_mod
    rows, n_exp, dim, width = k["experts"]
    wk = jax.random.split(keys[4], 5)
    w_gate, w_up = (jax.random.normal(wk[i], (n_exp, dim, width), dt)
                    * dim ** -0.5 for i in range(2))
    w_down = jax.random.normal(wk[2], (n_exp, width, dim), dt) * width ** -0.5
    xs = jax.random.normal(wk[3], (rows, dim), dt)
    owner = jnp.sort(jax.random.randint(wk[4], (rows,), 0, n_exp))
    group_sizes = jnp.sum(owner[:, None] == jnp.arange(n_exp)[None, :],
                          axis=0, dtype=jnp.int32)

    def plain_experts():
        """One expert at a time over every row, its own rows kept."""
        x32 = xs.astype(jnp.float32)

        def one(acc, e):
            with jax.default_matmul_precision("highest"):
                gate = x32 @ w_gate[e].astype(jnp.float32)
                up = x32 @ w_up[e].astype(jnp.float32)
                y = (jax.nn.silu(gate) * up) @ w_down[e].astype(jnp.float32)
            return acc + jnp.where((owner == e)[:, None], y, 0.0), None

        return jax.lax.scan(one, jnp.zeros((rows, dim), jnp.float32),
                            jnp.arange(n_exp))[0]

    @jax.jit
    def grouped():
        # the product takes and gives the ALIGNED layout: every expert's
        # rows from a multiple of 16 on, padding between them
        take, lie = grouped_ops.aligned_order(owner, group_sizes)
        return expert_mod.grouped_swiglu(
            xs[jnp.minimum(take, rows - 1)], w_gate, w_up, w_down,
            group_sizes)[lie]

    ok &= timed("grouped_experts", grouped, plain_experts)

    def ref_flash(q, k_, v):
        # float32 inputs AND float32 matmul passes (the TPU default for a
        # float32 dot is bf16 passes); the kernels keep their own precision.
        # Every query head is given its KV head: the kernels do that inside.
        n_rep = q.shape[2] // k_.shape[2]
        with jax.default_matmul_precision("highest"):
            return flash_ops.reference_attention(
                q.astype(jnp.float32),
                *(jnp.repeat(x.astype(jnp.float32), n_rep, axis=2)
                  for x in (k_, v)))

    def loss(attn):
        return lambda q, k_, v: jnp.sum(
            attn(q, k_, v).astype(jnp.float32) ** 2)

    flash = jax.jit(flash_ops.flash_attention)
    flash_grad = jax.jit(jax.grad(loss(flash_ops.flash_attention),
                                  argnums=(0, 1, 2)))
    ref_grad = jax.jit(jax.grad(loss(ref_flash), argnums=(0, 1, 2)))
    # (batch, length, query heads, KV heads, head size): equal heads, then
    # the grouped-query layout of the train cell, K and V unexpanded
    for name in ("flash", "flash_gqa"):
        fb, ft, fh, fhkv, fd = k[name]
        qf = jax.random.normal(keys[5], (fb, ft, fh, fd), dt)
        kf, vf = (jax.random.normal(keys[6 + i], (fb, ft, fhkv, fd), dt)
                  for i in range(2))
        ok &= timed(f"{name}_fwd", lambda: flash(qf, kf, vf),
                    lambda: ref_flash(qf, kf, vf))
        ok &= timed(f"{name}_bwd", lambda: flash_grad(qf, kf, vf),
                    lambda: ref_grad(qf, kf, vf))
    if chips > 1:
        out["tp_decode_collectives"] = tp_decode_collectives(rehearsal, chips)
        ok &= out["tp_decode_collectives"].get("all-reduce", 0) > 0
    out["ok"] = bool(ok)
    print(json.dumps(out))
    return 0 if ok else 1


def run_kernels(rehearsal: bool, chips: int, log_dir: str) -> dict:
    from ray_tpu.core import compile_cache

    env = dict(os.environ)
    compile_cache.configure(env)
    err_path = os.path.join(log_dir, "kernels.err")
    argv = [sys.executable, os.path.abspath(__file__), "--phase", "kernels",
            "--chips", str(chips)]
    if rehearsal:
        argv.append("--cpu-rehearsal")
    t0 = time.perf_counter()
    with open(err_path, "wb") as ferr:
        proc = subprocess.run(argv, env=env, cwd=HERE, stdout=subprocess.PIPE,
                              stderr=ferr, timeout=600)
    lines = proc.stdout.decode("utf-8", "replace").strip().splitlines()
    try:
        res = json.loads(lines[-1])
    except (IndexError, ValueError):
        raise SmokeFailure(
            f"kernels child exited {proc.returncode} without a result "
            f"(stderr: {err_path})") from None
    res["wall_s"] = round(time.perf_counter() - t0, 2)
    check(proc.returncode == 0 and res.get("ok"),
          f"kernels: exit {proc.returncode}, results {res.get('kernels')}")
    return res


def _init_runtime(rehearsal: bool, chips: int) -> None:
    """The head-mode runtime of one phase. On the chip the node agent finds
    the TPUs itself; the rehearsal DECLARES that many so placement takes
    the same path, onto workers that are held to the CPU."""
    import ray_tpu
    ray_tpu.init(num_cpus=max(8, os.cpu_count() or 1),
                 resources={"TPU": chips} if rehearsal else None)
    found = sum(n.get("resources", {}).get("TPU", 0)
                for n in ray_tpu.nodes())
    check(found >= chips, f"the node advertises {found} TPU chip(s), "
                          f"need {chips}")


def within(seconds: float, what: str, fn, *args):
    """Run one phase with a limit of its own: a hung placement or compile
    fails the run while there is still time to say which phase it was."""
    box: dict = {}

    def run():
        try:
            box["out"] = fn(*args)
        except BaseException as e:  # noqa: BLE001 - re-raised by the caller
            box["err"] = e

    t0 = time.perf_counter()
    th = threading.Thread(target=run, name=what, daemon=True)
    th.start()
    th.join(seconds)
    if th.is_alive():
        raise SmokeFailure(f"{what} phase still running after {seconds:.0f} s")
    if "err" in box:
        raise box["err"]
    print(f"chip_smoke: {what} ok in {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)
    return box["out"]


# ---------------------------------------------------------------------------
# phase 2: train
# ---------------------------------------------------------------------------

def train_loop(config: dict) -> None:
    """Runs on the TPU worker JaxTrainer places. The recipe is the one
    benchmark/train_cell.build gives its cell: fsdp over every chip,
    adafactor (adam's fp32 moments cost 8 bytes/param, most of one v5e's
    HBM at this size; factored state frees it for the "dots" remat policy)
    and weights made sharded on the devices."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu.train as rtrain
    from __graft_entry__ import collective_counts
    from ray_tpu.models import llama
    from ray_tpu.train import spmd

    devs = jax.devices()
    n = config["chips"]
    if len(devs) < n:
        raise RuntimeError(f"train needs {n} devices, jax has {len(devs)}")
    if config["rehearsal"]:
        cfg = llama.llama_tiny(attn_impl="flash", max_seq_len=config["seq"],
                               n_heads=8, n_kv_heads=4)
    else:
        if devs[0].platform != "tpu":
            raise RuntimeError(f"train worker is on {devs[0].platform!r}")
        cfg = llama.llama3_1b(max_seq_len=2048, remat_policy="dots",
                              ce_chunk=2048, ce_remat=False,
                              attn_impl="flash")
    mesh = spmd.make_mesh(n, devices=devs[:n])
    opt = spmd.default_optimizer(warmup_steps=10, decay_steps=1000,
                                 name="adafactor")
    state, sh = spmd.sharded_create_state(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg), opt, mesh,
        params_logical_axes=llama.logical_axes(cfg))
    step = spmd.make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh), opt, mesh, sh)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (config["batch"], config["seq"] + 1)), jnp.int32)
    batch = spmd.shard_batch({"tokens": tokens}, mesh)

    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    collectives = collective_counts(compiled.as_text())

    losses, step_s = [], []
    for _ in range(config["steps"]):
        t = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready(metrics["loss"])
        step_s.append(round(time.perf_counter() - t, 4))
        losses.append(float(metrics["loss"]))
    mem = [d.memory_stats() or {} for d in devs[:n]]
    rtrain.report({
        "losses": losses, "step_s": step_s,
        "compile_s": round(compile_s, 2),
        "params": llama.num_params(cfg),
        "mesh": {a: s for a, s in dict(mesh.shape).items() if s > 1},
        "collectives": collectives,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "per_device_bytes_in_use": [m.get("bytes_in_use") for m in mem],
        "per_device_peak_bytes": [m.get("peak_bytes_in_use") for m in mem],
    })


def run_train(rehearsal: bool, chips: int, sz: dict, tmp: str) -> dict:
    import math

    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    t0 = time.perf_counter()
    _init_runtime(rehearsal, chips)
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config={**sz["train"], "chips": chips,
                               "rehearsal": rehearsal},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": chips}),
            run_config=RunConfig(name="chip_smoke",
                                 storage_path=tmp)).fit()
        if result.error is not None:
            raise SmokeFailure(f"train: {result.error}")
        m = dict(result.metrics or {})
        losses = m.get("losses") or []
        check(len(losses) == sz["train"]["steps"],
              f"train: {len(losses)} of {sz['train']['steps']} steps")
        check(all(math.isfinite(x) for x in losses),
              f"train: non-finite loss {losses}")
        check(losses[-1] < losses[0], f"train: loss did not fall {losses}")
        if chips > 1:
            col = m.get("collectives") or {}
            check(col.get("all-gather", 0) + col.get("all-reduce", 0)
                  + col.get("reduce-scatter", 0) > 0,
                  f"train over {chips} chips compiled no collective: {col}")
            used = m.get("per_device_bytes_in_use") or []
            check(len(used) == chips and (rehearsal or (
                all(used) and max(used) <= 1.5 * min(used))),
                f"train: per-device memory not spread: {used}")
        m["store"] = _object_store_backend()
        m["wall_s"] = round(time.perf_counter() - t0, 2)
        m["run_s"] = round(sum(m.get("step_s") or []), 3)
        return m
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# phase 3: serve
# ---------------------------------------------------------------------------

_HEADERS = {"Content-Type": "application/json",
            # a first-use prefill program compiles under the first request
            "X-Request-Timeout-S": "600"}


def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers=_HEADERS)
    with urllib.request.urlopen(req, timeout=600) as r:
        return json.loads(r.read())


def _post_sse(url: str, payload: dict) -> dict:
    """Text, the final chunk and any in-stream error of one SSE request
    (the proxy keeps the per-chunk token ids to itself: text and the token
    count are what a client can compare)."""
    req = urllib.request.Request(
        url, data=json.dumps({**payload, "stream": True}).encode(),
        headers=_HEADERS)
    text: list[str] = []
    final: dict = {}
    error = None
    with urllib.request.urlopen(req, timeout=600) as r:
        for raw in r:
            line = raw.decode("utf-8", "replace").strip()
            if not line.startswith("data:"):
                continue
            body = line[5:].strip()
            if body == "[DONE]":
                break
            chunk = json.loads(body)
            text.extend(c.get("text", "") for c in chunk.get("choices", ()))
            error = error or chunk.get("error")
            if chunk.get("usage") is not None:
                final = chunk
    return {"text": "".join(text), "final": final, "error": error,
            "tokens": (final.get("usage") or {}).get("completion_tokens", 0)}


def _object_store_backend() -> str:
    """Which object store the node ended up with (make_store may switch to
    the python store when the native one cannot be built)."""
    from ray_tpu.core import api
    return api._head[1].store.backend_name


def run_serve(rehearsal: bool, chips: int, sz: dict) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMConfig, build_openai_app

    cfg = LLMConfig(
        model_id="llama-tiny" if rehearsal else "llama3-1b",
        model_config=serve_model(rehearsal), spec_decode_enabled=True,
        **sz["serve"])
    mt = sz["serve"]["max_tokens"]

    def prompt(n_tokens: int, lead: str = "") -> str:
        # the byte tokenizer adds BOS: n_tokens - 1 characters
        text = lead + "the quick brown fox jumps over the lazy dog " * (
            n_tokens // 40 + 1)
        return text[: n_tokens - 1]

    t0 = time.perf_counter()
    _init_runtime(rehearsal, chips)
    try:
        serve.run(build_openai_app(cfg, route_prefix="/v1"),
                  name="chip-smoke", route_prefix="/v1")
        proxy = serve.start_http_proxy(port=0)
        base = f"http://127.0.0.1:{proxy.port}/v1/completions"
        stats_url = f"http://127.0.0.1:{proxy.port}/v1/stats"
        ready_s = time.perf_counter() - t0

        def plain(p):
            out = _post(base, {"prompt": p, "max_tokens": mt})
            check("error" not in out and out.get("choices"),
                  f"serve: plain request failed: {out}")
            check(out["usage"]["completion_tokens"] >= 1,
                  f"serve: plain request returned no token: {out}")
            return out

        def stream(p):
            out = _post_sse(base, {"prompt": p, "max_tokens": mt})
            check(out["error"] is None and out["final"],
                  f"serve: SSE request failed: {out}")
            check(out["tokens"] >= 1, f"serve: SSE returned no token: {out}")
            return out

        # one prompt shorter than a page: its repeats take the same prefill
        # program (no prefix hit), so greedy tokens must repeat exactly
        p_short = prompt(sz["prompt_tokens"])
        t_req = time.perf_counter()
        first = plain(p_short)
        sse = stream(p_short)
        again = plain(p_short)
        sse_again = stream(p_short)
        check(first["choices"][0]["text"] == again["choices"][0]["text"]
              and first["usage"] == again["usage"],
              "serve: the repeated plain request answered differently")
        # (a stream decodes bytes chunk by chunk, so its text is compared
        # with another stream's, and with the plain answer only by count)
        check((sse["text"], sse["tokens"])
              == (sse_again["text"], sse_again["tokens"])
              and sse["tokens"] == first["usage"]["completion_tokens"],
              f"serve: greedy output changed between identical requests: "
              f"{sse['text']!r}/{sse['tokens']} vs "
              f"{sse_again['text']!r}/{sse_again['tokens']}")

        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            streams = list(pool.map(
                stream, [prompt(sz["prompt_tokens"], lead=f"{i} ")
                         for i in range(8)]))

        # chunked prefill, then the same prompt with another tail: the
        # shared full pages are a prefix hit and only the suffix is chunked
        shared = prompt(sz["shared_tokens"], lead="shared ")
        tail = sz["long_tokens"] - sz["shared_tokens"]
        long_a = stream(shared + ("a" * tail))
        s_mid = json.loads(urllib.request.urlopen(
            stats_url, timeout=60).read())
        long_b = stream(shared + ("b" * tail))
        stats = json.loads(urllib.request.urlopen(
            stats_url, timeout=60).read())
        traffic_s = time.perf_counter() - t_req

        check(stats["attention_backend"] == "pallas",
              f"serve: attention_backend={stats['attention_backend']!r}")
        check(stats["attn_decode_dispatches"] > 0
              and stats["attn_chunk_dispatches"] > 0,
              f"serve: no decode/chunk dispatch through the kernels")
        check(s_mid["attn_chunk_dispatches"] > 0,
              "serve: the long prompt was not chunk-prefilled")
        check(stats["prefix_hit_tokens"] > s_mid["prefix_hit_tokens"],
              "serve: the shared prefix was not a cache hit")
        check(stats["device_count"] == chips,
              f"serve: engine ran on {stats['device_count']} device(s)")
        if not rehearsal:
            check(stats["device_platform"] == "tpu"
                  and stats["attn_interpret"] == 0,
                  f"serve: engine ran on {stats['device_platform']!r}, "
                  f"attn_interpret={stats['attn_interpret']}")
            check(stats["device_bytes_in_use"] is not None,
                  "serve: the TPU reported no memory stats")
        if chips > 1:
            check(stats["tp_degree"] == chips
                  and stats["kv_shard_pool_bytes"] * chips
                  == stats["kv_pool_bytes"],
                  f"serve: KV pool not split {chips} ways")
        if chips > 1 and not rehearsal:
            # the fullest chip holds about a chips-th of weights + pool
            # (the replicated embedding comes on top), never all of it
            whole = stats["weights_bytes"] + stats["kv_pool_bytes"]
            check(stats["device_bytes_in_use"] < 0.6 * whole,
                  f"serve: one chip holds {stats['device_bytes_in_use']} "
                  f"of {whole} bytes")
        return {
            "wall_s": round(time.perf_counter() - t0, 2),
            "ready_s": round(ready_s, 2),
            "compile_s": stats["compile_s"],
            "run_s": round(traffic_s, 2),
            "requests": 6 + len(streams),
            "tokens_out": stats["tokens_out"],
            "long_prompt_tokens": long_a["final"]["usage"]["prompt_tokens"],
            "prefix_hit_tokens": stats["prefix_hit_tokens"],
            "store": _object_store_backend(),
            "stats": {k: stats[k] for k in (
                "attention_backend", "attn_interpret", "device_platform",
                "device_kind", "device_count", "tp_degree", "mesh_shape",
                "attn_decode_dispatches", "attn_verify_dispatches",
                "attn_chunk_dispatches", "attn_kernel_compiles",
                "compile_events", "mid_traffic_compiles", "weights_bytes",
                "kv_pool_bytes", "kv_shard_pool_bytes",
                "device_bytes_in_use", "device_peak_bytes")},
            "long_b_tokens": long_b["tokens"],
        }
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# process hygiene
# ---------------------------------------------------------------------------

def _descendants() -> list[int]:
    """Live (non-zombie) processes below this one."""
    parent: dict[int, int] = {}
    for stat in glob.glob("/proc/[0-9]*/stat"):
        try:
            with open(stat) as f:
                head, tail = f.read().rsplit(") ", 1)
        except OSError:
            continue
        fields = tail.split()
        if fields[0] != "Z":
            parent[int(head.split(" ", 1)[0])] = int(fields[1])
    out, frontier = [], {os.getpid()}
    while frontier:
        frontier = {p for p, pp in parent.items() if pp in frontier}
        out.extend(frontier)
    return out


def _kill_descendants() -> None:
    for pid in _descendants():
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass


def wait_chip_free(what: str) -> None:
    """Every process a phase started is gone before the next one starts:
    at no time do two live processes hold the chip."""
    deadline = time.monotonic() + 30.0
    while _descendants():
        if time.monotonic() > deadline:
            raise SmokeFailure(
                f"processes of the {what} phase outlived it: "
                f"{_descendants()}")
        time.sleep(0.2)


def assert_parent_off_chip() -> None:
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge
        check(not xla_bridge.backends_are_initialized(),
              "chip_smoke's parent process initialised a JAX backend")


def print_worker_stderr(log_dir: str, since: float) -> None:
    """Worker logs die with the machine: show the end of what the chip-
    holding workers wrote."""
    from ray_tpu.core.config import get_config
    dirs = [log_dir, get_config().log_dir or os.path.join(
        "/tmp/ray_tpu_logs", f"agent-{os.getpid()}")]
    paths = [p for d in dirs for p in glob.glob(os.path.join(d, "*.err"))
             if os.path.getsize(p) and os.path.getmtime(p) >= since]
    for path in sorted(paths, key=os.path.getmtime)[-4:]:
        with open(path, "rb") as f:
            f.seek(max(0, os.path.getsize(path) - 6000))
            tail = f.read().decode("utf-8", "replace")
        print(f"--- tail of {path}\n{tail}", file=sys.stderr)


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="chips the serve (TP) and train (fsdp) phases span")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="walk the control flow at toy sizes on the CPU; "
                         "prints platform cpu, proves nothing about a chip")
    ap.add_argument("--phase", choices=("kernels",), help=argparse.SUPPRESS)
    args = ap.parse_args()

    try:
        if not os.path.isdir(os.path.join(HERE, "ray_tpu")):
            raise ImportError("no ray_tpu/ beside chip_smoke.py")
        import ray_tpu  # noqa: F401
    except ImportError:
        print("chip_smoke.py: the ray_tpu package is not beside this "
              "script; run it from the root of a checkout",
              file=sys.stderr)
        return 2
    if args.phase == "kernels":
        return kernels_child(args.cpu_rehearsal, args.chips)

    rehearsal = args.cpu_rehearsal
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}")
    else:
        from ray_tpu.parallel.topology import local_chip_count
        found = local_chip_count()
        if found < args.chips:
            print(f"chip_smoke.py: needs {args.chips} TPU chip(s) and found "
                  f"{found} (no chip device nodes, or JAX_PLATFORMS pins jax "
                  f"to the cpu); there is no CPU mode", file=sys.stderr)
            return 3

    def out_of_time():
        print("chip_smoke.py: out of time", file=sys.stderr)
        _kill_descendants()
        os._exit(4)

    watchdog = threading.Timer(DEADLINE_S, out_of_time)
    watchdog.daemon = True
    watchdog.start()

    started = time.time()
    t0 = time.perf_counter()
    sz = sizes(rehearsal, args.chips)
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    device = None                # as jax reports it in the kernels child
    try:
        kern = within(400, "kernels", run_kernels, rehearsal, args.chips,
                      tmp)
        device = kern["device"]
        if not rehearsal:
            check(device["platform"] == "tpu"
                  and device["count"] >= args.chips,
                  f"jax found {device}, need {args.chips} TPU device(s)")
        wait_chip_free("kernels")
        assert_parent_off_chip()
        train = within(500, "train", run_train, rehearsal, args.chips, sz,
                       tmp)
        wait_chip_free("train")
        assert_parent_off_chip()
        serve_res = within(800, "serve", run_serve, rehearsal, args.chips,
                           sz)
        wait_chip_free("serve")
        assert_parent_off_chip()
        for name, dev in (("train", train["device"]),
                          ("serve", {"platform":
                                     serve_res["stats"]["device_platform"],
                                     "kind":
                                     serve_res["stats"]["device_kind"]})):
            check(dev["platform"] == device["platform"]
                  and dev["kind"] == device["kind"],
                  f"{name} ran on {dev}, kernels on {device}")
    except Exception as e:  # noqa: BLE001 - every failure ends the run
        print(f"chip_smoke.py FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        if not isinstance(e, SmokeFailure):
            import traceback
            traceback.print_exc()
        print_worker_stderr(tmp, started)
        _kill_descendants()
        if device is not None and (rehearsal or device["platform"] == "tpu"):
            print_result(False, device)
        return 1
    finally:
        watchdog.cancel()

    print(json.dumps({"report": {
        "chips": args.chips,
        "device": device,
        "jax": kern["jax"], "jaxlib": kern["jaxlib"],
        "libtpu": kern["libtpu"],
        "wall_s": round(time.perf_counter() - t0, 1),
        "phases": {
            "kernels": {
                "wall_s": kern["wall_s"],
                "compile_s": round(sum(v["compile_s"] for v in
                                       kern["kernels"].values()), 2),
                "run_s": round(sum(v["run_s"] for v in
                                   kern["kernels"].values()), 3),
                "interpret": kern["interpret"],
                "tp_decode_collectives": kern.get("tp_decode_collectives"),
                "max_abs_err": {k: round(v["max_abs_err"], 5)
                                for k, v in kern["kernels"].items()}},
            "train": train,
            "serve": serve_res},
        "mid_traffic_compiles": serve_res["stats"]["mid_traffic_compiles"],
        "object_store": serve_res["store"],
        "claim": None,
    }}))
    print_result(True, device)
    return 0


def print_result(ok: bool, device: dict) -> None:
    """The last line of standard output: exactly ``ok`` and ``device``, the
    device exactly ``platform``, ``kind``, ``count``."""
    sys.stderr.flush()
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
