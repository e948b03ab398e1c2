"""One run of a train cell through JaxTrainer.fit(): one worker that holds
the cell's chips, the step recipe of bench.py (flash attention under
shard_map, dots remat, adafactor, bf16 parameters, fsdp over every chip),
steps back to back for the window on the seed's cycle of batches
(traffic/train_batches.py).

The loop itself (``train_loop``) runs on the worker; this process never
initialises a jax backend.
"""

from __future__ import annotations

import os
import shutil
import time

from benchmark import checks, common

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "collective-permute", "all-to-all")


def train_loop(config: dict) -> None:
    """On the worker: JaxTrainer's train_loop_per_worker. Reports once."""
    import ray_tpu.train as rtrain
    rtrain.report(train_steps(config))


def build(family: str, sz: dict, tr: dict, chips: int,
          rehearsal: bool) -> dict:
    """Everything of a run that does not depend on the seed: the mesh, the
    optimizer, the shardings, the jitted init (the key is an argument) and
    the jitted step. bench._make_step's recipe, on the model the family's
    adapter (benchmark/models/<family>.py) builds."""
    import jax

    from benchmark.checks import require_device
    from ray_tpu.train import spmd

    fam = common.load_module("models", family)
    device = require_device(chips, rehearsal)
    devs = jax.devices()[:chips]
    cfg = fam.model_config(sz, trainer=tr)
    mesh = spmd.make_mesh(chips, devices=devs, **tr["mesh"])
    opt = spmd.default_optimizer(learning_rate=tr["learning_rate"],
                                 warmup_steps=10, decay_steps=1000,
                                 name=tr["optimizer"])
    shapes = jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg))
    sh = spmd.state_shardings(fam.logical_axes(cfg), shapes, mesh, opt)
    # weights made sharded on the devices in one jitted call from the key
    # (spmd.sharded_create_state's body, with the key as an argument)
    init_state = jax.jit(
        lambda key: spmd.TrainState.create(fam.init_params(key, cfg), opt),
        out_shardings=sh)
    init_params = jax.jit(lambda key: fam.init_params(key, cfg),
                          out_shardings=sh.params)
    step = spmd.make_train_step(
        lambda p, b: fam.loss_fn(p, b, cfg, mesh), opt, mesh, sh)
    return {"fam": fam, "cfg": cfg, "vocab_size": sz["vocab_size"],
            "mesh": mesh, "sh": sh, "init_state": init_state,
            "init_params": init_params, "step": step, "compiled": None,
            "device": device, "devs": devs, "tr": tr}


def train_steps(config: dict, built: dict | None = None) -> dict:
    """Set-up, the window of steps, and the reference loss of step 0, in
    the process that holds the chips."""
    import jax
    import jax.numpy as jnp

    from benchmark.traffic import train_batches
    from ray_tpu.train import spmd

    t_enter = time.time()
    phases = {}
    t = time.perf_counter()
    if built is None:
        built = build(config["family"], config["sizes"], config["trainer"],
                      config["chips"], config["rehearsal"])
    fam, cfg, mesh, tr = (built[k] for k in ("fam", "cfg", "mesh", "tr"))
    key = common.fold_seed(config["seed"])
    state = built["init_state"](key)
    gb, seq = tr["global_batch"], tr["seq_len"]

    cycle = config["plan"]["distinct_batches"]

    def batch(i):
        return spmd.shard_batch({"tokens": jnp.asarray(train_batches.batch(
            config["seed"], i % cycle, gb, seq, built["vocab_size"]))}, mesh)

    jax.block_until_ready(state.step)
    phases["init_state"] = time.perf_counter() - t
    t = time.perf_counter()
    if built["compiled"] is None:
        built["compiled"] = built["step"].lower(state, batch(0)).compile()
        ma = built["compiled"].memory_analysis()
        built["program_bytes"] = None if ma is None else int(
            ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        hlo = built["compiled"].as_text()
        built["collectives"] = {
            op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
            for op in COLLECTIVE_OPS}
    compiled = built["compiled"]
    phases["compile_step"] = time.perf_counter() - t

    losses, n = [], 0
    t = time.perf_counter()
    for _ in range(max(1, tr["warmup_steps"])):
        state, m = compiled(state, batch(n))
        losses.append(float(m["loss"]))
        n += 1
    phases["warm_steps"] = time.perf_counter() - t

    trace_dir, trace_steps = config.get("trace_dir"), config["trace_steps"]
    t_window = time.time()
    t0 = time.perf_counter()
    step_s, tracing, traced = [], False, []
    while time.perf_counter() - t0 < config["seconds"]:
        if trace_dir and not traced and not tracing and len(step_s) >= 2:
            jax.profiler.start_trace(trace_dir)
            tracing = True
        ts = time.perf_counter()
        state, m = compiled(state, batch(n))
        losses.append(float(m["loss"]))       # the device sync
        step_s.append(time.perf_counter() - ts)
        n += 1
        if tracing:
            traced.append(len(step_s) - 1)
            if len(traced) >= trace_steps:
                jax.profiler.stop_trace()
                tracing = False
    window_wall_s = time.perf_counter() - t0   # first start to last sync
    if tracing:
        jax.profiler.stop_trace()
    mem = [d.memory_stats() or {} for d in built["devs"]]

    # the reference loss of step 0: same batch, same initial parameters
    # (made again from the seed), float32, after the state has gone
    t = time.perf_counter()
    del state
    ref0 = common.reference(fam).loss(
        built["init_params"](key),
        train_batches.batch(config["seed"], 0, gb, seq,
                            built["vocab_size"]),
        **fam.reference_kwargs(cfg))
    phases["reference_loss"] = time.perf_counter() - t
    last_same = max(i for i in range(len(losses)) if i % cycle == 0)
    return {
        "losses": losses, "window_step_s": step_s, "traced_steps": traced,
        "window_wall_s": window_wall_s,
        # first and last loss on batch 0 of the cycle
        "loss_first": losses[0], "loss_last_same_batch": losses[last_same],
        "program_bytes": built["program_bytes"], "memory_stats": mem,
        "t_enter": t_enter, "t_window": t_window, "phases_s": phases,
        "reference_first_loss": float(ref0),
        "collectives": built["collectives"],
        "params": fam.num_params(cfg), "device": built["device"],
        "mesh": {a: s for a, s in dict(mesh.shape).items() if s > 1},
        }


def run(entry: dict, cell: dict, config: dict, args, t_process: float) -> dict:
    import ray_tpu
    from ray_tpu.core import compile_cache
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    rehearsal, chips = args.rehearsal, entry["chips"]
    tr = common.section(config, "trainer", rehearsal)
    traffic = (cell["rehearsal"] if rehearsal else cell)["traffic"]
    sz = common.family(config).sizes(config, rehearsal)
    out_dir = os.path.join(common.ROOT, ".bench_out", entry["name"])
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(out_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    compile_cache.configure()
    report: dict = {"phases_s": {}, "rehearsal": rehearsal}
    t = time.monotonic()
    ray_tpu.init(num_cpus=max(8, os.cpu_count() or 1),
                 resources={"TPU": chips} if rehearsal else None)
    try:
        found = sum(n.get("resources", {}).get("TPU", 0)
                    for n in ray_tpu.nodes())
        if found < chips:
            raise common.BenchError(f"the node has {found} TPU chip(s), "
                                    f"the cell needs {chips}")
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "family": config["model_family"], "sizes": sz, "trainer": tr, "chips": chips,
                "rehearsal": rehearsal, "seed": args.seed,
                "seconds": args.seconds, "trace_dir": trace_dir,
                "plan": common.load_module(
                    "traffic", traffic["generator"]).plan(
                        traffic, args.seed, args.seconds),
                "trace_steps": (cell["rehearsal"] if rehearsal else cell).get(
                    "trace_steps", 3)},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": chips}),
            run_config=RunConfig(name=entry["name"],
                                 storage_path=os.path.join(out_dir, "runs"))
        ).fit()
        if result.error is not None:
            raise common.BenchError(f"JaxTrainer.fit(): {result.error}")
        m = dict(result.metrics or {})
    finally:
        ray_tpu.shutdown()
    report["phases_s"]["fit"] = time.monotonic() - t
    report["phases_s"]["children_gone"] = common.wait_children_gone(30.0)
    report["phases_s"].update(
        {f"worker_{k}": v for k, v in m["phases_s"].items()})
    report["phases_s"]["worker_start"] = m["t_enter"] - t_process
    spec = common.section(config, "checks", rehearsal)["first_loss"]
    structure = checks.train_structure_check(
        [float(x) for x in m["losses"]], m["reference_first_loss"],
        spec["tolerance"], float(m["loss_last_same_batch"]))
    # peak_bytes_in_use misses the step program's temporaries (it read
    # 2.82 GB beside 2.77 GB of state); peak_bytes_reserved holds them
    # (12.74 GB). The compiled program's memory_analysis (19.8 GB of a
    # 16 GB chip) overstates and stays on the report line only.
    peak = max(max(s.get("peak_bytes_in_use") or 0,
                   s.get("peak_bytes_reserved") or 0)
               for s in m["memory_stats"])
    return {
        "kind": "train", "sizes": sz, "setup_s": m["t_window"] - t_process,
        "train": {"window_step_s": m["window_step_s"],
                  "window_wall_s": m["window_wall_s"],
                  "tokens_per_step": tr["global_batch"] * tr["seq_len"],
                  "seq_len": tr["seq_len"], "chips": chips,
                  "global_batch": tr["global_batch"], "losses": m["losses"]},
        "trace_dir": trace_dir if m["traced_steps"] else None,
        "device": {**m["device"], "memory_peak_bytes": int(peak)},
        "checks": {"structure": structure}, "report": report,
        "extra": {k: m[k] for k in (
            "collectives", "params", "mesh", "traced_steps", "program_bytes",
            "memory_stats")},
    }
