"""Benchmark: Llama pretraining step throughput (tokens/sec/chip).

North-star metric per BASELINE.json ("Ray Train tokens/sec/chip @
Llama-3-8B"); the reference repo publishes no number for it ("published": {}),
so vs_baseline reports model-FLOPs utilization (MFU) against the chip's bf16
roofline instead (1.0 = peak matmul throughput).

Runs an A/B over attention implementations (dense einsum vs the Pallas flash
kernel, ops/attention.py) on the largest Llama config that fits the visible
chip, and reports the better one as the headline with both in "extra".
The true 8B config needs a v5p-64 pod (BASELINE target); one v5e chip tops
out around ~2B params with remat+bf16, so the bench scales the config to the
chip and says so rather than faking the 8B label.

Runs on a TPU or not at all: no accelerator, an unknown ``device_kind`` or
a failed arm is an error, never a smaller model under the same metric name.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
"""

from __future__ import annotations

import json
import sys
import time

from ray_tpu.core import compile_cache

compile_cache.configure()  # before jax reads its environment

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# bf16 peak TFLOP/s per chip by jax ``device_kind`` (Google Cloud TPU
# documentation, per-generation system architecture pages). A device that
# is not here is an error, not a default.
_PEAK_TFLOPS = {
    "TPU v4": 275.0, "TPU v5 lite": 197.0, "TPU v5": 459.0,
    "TPU v6 lite": 918.0,
}


def _peak_tflops(device) -> float:
    try:
        return _PEAK_TFLOPS[device.device_kind]
    except KeyError:
        raise SystemExit(
            f"bench.py: no bf16 peak known for device_kind="
            f"{device.device_kind!r}; add it to _PEAK_TFLOPS with its "
            f"source") from None


def _make_step(cfg, devices, optimizer: str):
    """Shared recipe for the static-batch and data-plane runs AND for
    chip_smoke.py's train phase — one copy, so every run trains the same
    way. ``devices``: the chips the mesh spans (fsdp over all of them)."""
    from ray_tpu.models import llama
    from ray_tpu.train import spmd

    mesh = spmd.make_mesh(len(devices), devices=devices)
    # adafactor: adam's fp32 moments cost 8 bytes/param — most of one v5e's
    # HBM at 1.5B params; factored state frees it for the "dots" remat
    # policy (saved matmul outputs, no backward recompute), the single
    # biggest measured MFU lever on this chip
    opt = spmd.default_optimizer(warmup_steps=10, decay_steps=1000,
                                 name=optimizer)
    state, sh = spmd.sharded_create_state(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg), opt, mesh,
        params_logical_axes=llama.logical_axes(cfg))
    step = spmd.make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh), opt, mesh, sh)
    return mesh, state, step


def _run_config(cfg, batch: int, seq: int, steps: int, warmup: int, dev,
                optimizer: str = "adafactor"):
    from ray_tpu.train import spmd

    mesh, state, step = _make_step(cfg, [dev], optimizer)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, seq + 1)), jnp.int32)
    batch_data = spmd.shard_batch({"tokens": tokens}, mesh)

    for _ in range(warmup):
        state, metrics = step(state, batch_data)
    jax.block_until_ready(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch_data)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    return batch * seq * steps / dt


def _run_data_pipeline(cfg, batch: int, seq: int, steps: int, warmup: int,
                       dev, optimizer: str = "adafactor") -> float:
    """Same train step, but batches arrive through the REAL Data plane:
    synthetic tokens generated in Data tasks -> streaming_split ->
    iter_jax_batches HBM double-buffering (reference:
    release/train_tests/benchmark/train_benchmark.py drives training
    through ray.data the same way). Returns tokens/s; the delta vs the
    static-batch path is the input-pipeline cost."""
    from ray_tpu import data as rdata
    from ray_tpu.train import spmd

    mesh, state, step = _make_step(cfg, [dev], optimizer)
    n_rows = (steps + warmup) * batch
    vocab = cfg.vocab_size
    seqlen = seq

    def gen_tokens(b: dict) -> dict:
        rng = np.random.default_rng(int(b["id"][0]))
        return {"tokens": rng.integers(
            0, vocab, (len(b["id"]), seqlen + 1)).astype(np.int32)}

    ds = rdata.range(n_rows).map_batches(gen_tokens, batch_size=batch)
    (it,) = ds.streaming_split(1)
    sharding = spmd.batch_sharding(mesh, extra_dims=1)
    batches = it.iter_jax_batches(batch_size=batch, sharding=sharding,
                                  prefetch_batches=2)

    for _ in range(warmup):
        state, metrics = step(state, next(batches))
    if warmup:
        jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    n = 0
    for batch_data in batches:
        state, metrics = step(state, batch_data)
        n += 1
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    return batch * seq * n / dt


def main() -> None:
    import dataclasses

    from ray_tpu.models import llama

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py: jax found no TPU (platform={dev.platform!r}); this "
            f"benchmark has no CPU mode")
    peak = _peak_tflops(dev)
    # Measured recipe for one v5e chip at 1.5B params / seq 2048 (the 8B
    # config's sequence length; the 8B model itself needs a pod —
    # BASELINE's v5p-64): flash attention + "dots" remat (no backward
    # recompute) + adafactor + batch 4. Sweep results on this chip:
    # full-remat b8 flash 0.446 MFU, dots b4 flash 0.49-0.51, dense
    # dots b4 0.42, 3.6B full-remat b4 0.39.
    # ce_remat=False: keep the CE chunk's fp32 logits as residuals
    # instead of recomputing the lm_head matmul in backward — the
    # 4.2 GB residual fits at b4 and buys ~33 ms/step (r5 CE lever)
    base = llama.llama3_1b(max_seq_len=2048, remat_policy="dots",
                           ce_chunk=2048, ce_remat=False)
    batch, seq, steps, warmup = 4, 2048, 10, 3
    optimizer = "adafactor"  # frees adam's 12GB of fp32 moments for dots

    # an arm that fails fails the run: the exception propagates
    results: dict[str, float] = {}
    for impl in ("dense", "flash"):
        cfg = dataclasses.replace(base, attn_impl=impl)
        results[impl] = _run_config(cfg, batch, seq, steps, warmup, dev,
                                    optimizer=optimizer)
    best_impl = max(results, key=results.get)
    tok_per_s = results[best_impl]

    # Data-plane A/B: the same step fed through streaming_split ->
    # iter_jax_batches (tokens generated in Data tasks). Reported as the
    # input-pipeline cost vs the static-batch headline.
    import os as _os

    import ray_tpu
    from ray_tpu.core import config as _cfgmod
    try:
        # Honest overlap: cap the executor's output buffering so block
        # generation CANNOT pre-complete during warmup (13 tiny blocks
        # would otherwise all materialize before t0 and the "pipeline
        # cost" would measure queue pulls only), and run 3x the steps
        # so most generation lands inside the timed region.
        _os.environ.setdefault("RAY_TPU_DATA_OP_OUTPUT_BUFFER_BYTES",
                               str(64 * 1024))
        _cfgmod.reset_config()
        ray_tpu.init(num_cpus=4)
        cfg = dataclasses.replace(base, attn_impl=best_impl)
        data_tps = round(_run_data_pipeline(
            cfg, batch, seq, steps * 3, warmup, dev,
            optimizer=optimizer), 1)
    finally:
        ray_tpu.shutdown()

    n_params = llama.num_params(base)

    def mfu(tps: float) -> float:
        return round((6.0 * n_params * tps) / (peak * 1e12), 4)

    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tok_per_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": mfu(tok_per_s),
        "extra": {
            "attn_impl": best_impl,
            "per_impl_tokens_per_s": {k: round(v, 1)
                                      for k, v in results.items()},
            "per_impl_mfu": {k: mfu(v) for k, v in results.items()},
            "params": n_params,
            "batch": batch, "seq": seq,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "data_pipeline_tokens_per_s": data_tps,
            "data_pipeline_cost_pct": round(
                100.0 * (1.0 - data_tps / tok_per_s), 2),
        },
    }))


if __name__ == "__main__":
    sys.exit(main())
