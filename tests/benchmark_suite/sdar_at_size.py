"""Check 1 of ``sdar-30b-a3b-chat-serve-1chip`` at the published widths, on
the chip, seed after seed: what its four limits are read from.

    chiprun -- python3 tests/benchmark_suite/sdar_at_size.py <seed> ...

Per seed the routing-aware comparison as the cell runs it, under the
limits the configuration's file commits (``ok`` is check 1's own verdict,
``failed_by`` the limits that refused it); the same with the experts, and
with every matrix of the model, on a per-tensor int8 grid (the nearest
precision below the configuration's bfloat16:
it has to come out as not correct); on the first seed also the reference
with one rule left out (q/k norm, norm_topk_prob, the block mask replaced
by the causal one). ``--int8-only`` leaves the sound comparison out (a run
of the cell on that seed has read it); ``--rehearsal`` walks the script at
the tiny preset on the CPU (no device number comes of it). Written to
chiprun_out/sdar_at_size.json. This process holds the chip.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _int8(w):
    """A matrix rounded to an int8 grid, one scale a tensor."""
    import jax.numpy as jnp
    w32 = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w32)) / 127.0
    return (jnp.round(w32 / s).clip(-127, 127) * s).astype(w.dtype)


def int8_experts(params):
    """Every expert matrix on a per-tensor int8 grid."""
    return dict(params, layers=[
        dict(lp, moe=dict(lp["moe"], **{k: _int8(lp["moe"][k]) for k in (
            "w_gate", "w_up", "w_down")})) for lp in params["layers"]])


def int8_weights(params):
    """Every matrix of the model (attention, experts, embedding, head) on a
    per-tensor int8 grid; norms and router as they are."""
    import jax
    keep = ("norm", "router")
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w if any(k in jax.tree_util.keystr(path)
                                 for k in keep) else _int8(w), params)


def main(argv: list[str]) -> int:
    import jax

    from benchmark import checks, common
    from ray_tpu.core import compile_cache
    compile_cache.configure()
    sound = "--int8-only" not in argv
    rehearsal = "--rehearsal" in argv       # tiny preset on the CPU
    seeds = [int(a) for a in argv if a.isdigit()] or [3700200001]
    config = common.load_cell("sdar-30b-a3b-serve-decode")[2]
    fam = common.family(config)
    sz = fam.sizes(config, rehearsal)
    eng = common.section(config, "engine", rehearsal)
    chk = common.section(config, "checks", rehearsal)["logits"]
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "seeds": [], "controls": []}

    def brief(res):
        r = res["routing"]
        failed_by = [name for name, got, limit in (
            ("tolerance", res["max_abs_err"], res["tolerance"]),
            ("rms_tolerance", res["rms_err"], res["rms_tolerance"]),
            ("routing_slack", r["max_slack"], r["slack_limit"]),
            ("routing_flip_share_max", r["flip_share"], r["flip_share_max"]))
            if not got <= limit]
        return {"ok": res["ok"], "failed_by": failed_by,
                "max_abs_err": res["max_abs_err"], "rms_err": res["rms_err"],
                "backend": res["backend"],
                "ref_max_abs": max(e["ref_max_abs"]
                                   for e in res["errors"].values()),
                **{k: r[k] for k in (
                    "decisions", "flipped", "flip_share", "max_slack")}}

    path = os.path.join(ROOT, "chiprun_out", "sdar_at_size.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    for i, seed in enumerate(seeds):
        row = {"seed": seed}
        if sound:
            row.update(brief(checks.logits_check(fam, sz, eng, chk, seed)))
        row["int8_experts"] = brief(checks.logits_check(
            fam, sz, eng, chk, seed, mutate=int8_experts))
        row["int8_weights"] = brief(checks.logits_check(
            fam, sz, eng, chk, seed, mutate=int8_weights))
        if i < 1:
            for name, kw in (("no_qk_norm", {"qk_norm": False}),
                             ("no_norm_topk", {"norm_topk": False}),
                             ("causal_mask", {"block_mask": False})):
                row[name] = brief(checks.logits_check(
                    fam, sz, eng, chk, seed, **kw))
        out["seeds"].append(row)
        print(json.dumps(row), flush=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
