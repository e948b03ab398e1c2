"""The toy routed family's check 1 and 2 at ISSUE 29's widths, on the chip:

    chiprun -- python3 tests/benchmark_suite/routed_toy/at_size.py [seeds]

This parent never touches jax. Its children, one after another: (1)
``measure``: per seed the strict comparison, the routing-aware one with
the routing counts, and the same with the experts on an int8 grid (one
scale a tensor, and one a column), on three seeds the other controls, and
check 2's deficits on tokens the toy's programs decode greedily (own
routing in the reference), with one replaced token as its control; (2)
benchmark/checks.py
in a copy of benchmark/ with the family dropped in, not a rehearsal, on a
configuration that states ``backend: gather`` with its reason: it has to
reach check 1 and print its line; (3) the same without the two keys: it
has to exit with 3. Everything is written to chiprun_out/routed_at_size.json.
``--rehearsal`` walks the same control flow at the tiny preset on the CPU
(no device number comes of it)."""

from __future__ import annotations

import functools
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path[:0] = [ROOT, HERE]
OUT = os.path.join(ROOT, "chiprun_out", "routed_at_size.json")
FAULT_SEEDS = 3
LENGTHS = {False: ([102, 198, 330, 643], 96), True: ([12, 30, 70], 24)}


def measure(n_seeds: int, rehearsal: bool) -> dict:
    import jax

    import drive
    from benchmark import checks, common
    from ray_tpu.core import compile_cache
    compile_cache.configure()
    fam, config = drive.FAM, drive.CONFIG
    sz = fam.sizes(config, rehearsal)
    eng = common.section(config, "engine", rehearsal)
    chk = dict(common.section(config, "checks", rehearsal)["logits"],
               tolerance=float("inf"), rms_tolerance=float("inf"),
               routing_slack=float("inf"), routing_flip_share_max=1.0)
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rehearsal": rehearsal, "sizes": sz, "seeds": [], "controls": [],
           "served": []}
    seeds = [2900100001 + 1009 * i for i in range(n_seeds)]

    def brief(res):
        return {"max_abs_err": res["max_abs_err"],
                "rms_err": res["rms_err"],
                "ref_max_abs": max(e["ref_max_abs"]
                                   for e in res["errors"].values()),
                **{k: res["routing"][k] for k in (
                    "decisions", "flipped", "flip_share", "max_slack")},
                "program_s": res["program_s"],
                "reference_s": res["reference_s"]}

    for seed in seeds:
        forced = checks.logits_check(fam, sz, eng, chk, seed)
        strict = checks.logits_check(drive.STRICT, sz, eng, chk, seed)
        out["seeds"].append(dict(
            brief(forced), seed=seed, strict_max_abs_err=strict["max_abs_err"],
            int8_experts=brief(checks.logits_check(
                fam, sz, eng, chk, seed, mutate=drive.int8_experts)),
            int8_experts_per_channel=brief(checks.logits_check(
                fam, sz, eng, chk, seed, mutate=functools.partial(
                    drive.int8_experts, per_channel=True)))))
        print(json.dumps(out["seeds"][-1]), file=sys.stderr, flush=True)
    for seed in seeds[:FAULT_SEEDS]:
        row = {"seed": seed}
        for fault in fam.FAULTS[1:]:
            row[fault] = brief(checks.logits_check(
                fam, dict(sz, fault=fault), eng, chk, seed))
        out["controls"].append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    # check 2: the served weights are init_params(PRNGKey(0)) at full depth
    cfg = fam.model_config(sz)
    params = jax.block_until_ready(fam.init_params(jax.random.PRNGKey(0), cfg))
    ref, kw = common.reference(fam), fam.reference_kwargs(cfg)
    lengths, n_out = LENGTHS[rehearsal]
    for seed in seeds[:max(FAULT_SEEDS, n_seeds // 2)]:
        samples = drive.served_samples(cfg, params, eng, seed, lengths, n_out)
        sound = checks.served_tokens_check(ref, kw, params, samples,
                                           float("inf"), eos=None)
        bad = [dict(s, tokens=list(s["tokens"])) for s in samples]
        bad[0]["tokens"][1] = (bad[0]["tokens"][1] + 7) % sz["vocab_size"]
        control = checks.served_tokens_check(ref, kw, params, bad,
                                             float("inf"), eos=None)
        out["served"].append({
            "seed": seed, "max_deficit": sound["max_deficit"],
            "per_sample": sound["per_sample_max_deficit"],
            "tokens_checked": sound["tokens_checked"],
            "replaced_token_deficit": control["max_deficit"]})
        print(json.dumps(out["served"][-1]), file=sys.stderr, flush=True)
        out["samples"] = samples          # the last seed's go to the child
    return out


def limits(m: dict) -> dict:
    """Provisional limits for the children, from this very run: largest
    seen x 1.5 (logits), x 3 (slack, flip share), x 2 (margin); the
    root-mean-square error half way (in ratio) between the sound runs'
    largest and the int8 experts' smallest."""
    rows = m["seeds"]
    return {"tolerance": 1.5 * max(r["max_abs_err"] for r in rows),
            "rms_tolerance": (
                max(r["rms_err"] for r in rows)
                * min(r["int8_experts"]["rms_err"] for r in rows)) ** 0.5,
            "routing_slack": 3.0 * max(r["max_slack"] for r in rows),
            "routing_flip_share_max": min(1.0, 3.0 * max(
                r["flip_share"] for r in rows)),
            "margin": 2.0 * max(r["max_deficit"] for r in m["served"])}


def drop_in(copy: str) -> None:
    """A copy of benchmark/ beside the program, with the family's files
    dropped into it and none that was there edited."""
    shutil.rmtree(copy, ignore_errors=True)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(copy, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(HERE, "benchmark"),
                    os.path.join(copy, "benchmark"), dirs_exist_ok=True)
    os.symlink(os.path.join(ROOT, "ray_tpu"), os.path.join(copy, "ray_tpu"))


def checks_child(copy: str, spec: dict, name: str) -> dict:
    path = os.path.join(copy, f"{name}.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    proc = subprocess.run(
        [sys.executable, os.path.join(copy, "benchmark", "checks.py"), path],
        cwd=copy, capture_output=True, text=True, timeout=1500)
    lines = proc.stdout.strip().splitlines()
    return {"rc": proc.returncode,
            "line": json.loads(lines[-1]) if lines else None,
            "stderr_tail": proc.stderr[-600:]}


def main(argv: list[str]) -> int:
    rehearsal = "--rehearsal" in argv
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if argv[:1] == ["measure"]:
        print(json.dumps(measure(int(argv[1]), rehearsal)))
        return 0
    n_seeds = int(([a for a in argv if a.isdigit()] or ["20"])[0])
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "measure", str(n_seeds)]
        + (["--rehearsal"] if rehearsal else []), stdout=subprocess.PIPE,
        text=True, cwd=ROOT, timeout=3000)
    if proc.returncode != 0:
        return proc.returncode
    m = json.loads(proc.stdout.strip().splitlines()[-1])
    samples = m.pop("samples")
    lim = limits(m)
    import drive                     # loads the family; touches no backend
    config = drive.CONFIG
    section = (config["rehearsal"] if rehearsal else config)
    logits = dict(section["checks"]["logits"], **{
        k: lim[k] for k in ("tolerance", "rms_tolerance", "routing_slack",
                            "routing_flip_share_max")})
    spec = {"rehearsal": rehearsal, "chips": 1, "seed": 2900100001,
            "family": config["model_family"], "sizes": m["sizes"],
            "engine": section["engine"], "samples": samples,
            "checks": {"logits": logits, "served_tokens": {
                "sample": len(samples), "margin": lim["margin"]}}}
    copy = os.path.join(ROOT, ".bench_out", "routed_copy")
    drop_in(copy)
    m["provisional_limits"] = lim
    m["child_gather_stated"] = checks_child(copy, spec, "stated")
    silent = json.loads(json.dumps(spec))
    for key in ("backend", "backend_why"):
        silent["checks"]["logits"].pop(key, None)
    m["child_backend_not_stated"] = checks_child(copy, silent, "silent")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(m, f, indent=1)
    for key in ("child_gather_stated", "child_backend_not_stated"):
        c = m[key]
        print(key, c["rc"], c["line"] and {
            k: c["line"][k].get("ok") for k in ("logits", "served_tokens")},
            c["stderr_tail"][-200:] if c["rc"] else "")
    print(json.dumps(lim))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
