"""Check 1 of ``joyai-llm-flash-serve-1chip`` at the published widths, on the
chip, seed after seed: what its four limits are read from.

    chiprun -- python3 tests/benchmark_suite/joyai_at_size.py <seed> ...

Per seed the routing-aware comparison as the cell runs it, under the limits
the configuration's file commits (``ok`` is check 1's own verdict,
``failed_by`` the limits that refused it); the same with every matrix of
the model on a per-tensor int8 grid, and with THE LATENT POOL held on an
int8 grid (each the nearest precision below the configuration's bfloat16:
both have to come out as not correct). On the first ``--controls N`` seeds
(default 1) also the reference with one rule left out or wrong
(benchmark/reference/joyai_f32.py lists them): the softmax scale of 128 or
of 576 lanes in place of 192, ``k_r`` unrotated, the norm of ``c_kv`` or of
``c_q`` left out, the values read from lanes 64-575, the shared expert, the
factor 2.5 or the selection bias left out. ``--controls-only`` leaves the
sound comparison out (a run of the cell on that seed has read it);
``--rehearsal`` walks the script at the tiny preset on the CPU (no device
number comes of it). Written to chiprun_out/pr44/joyai_at_size.json. This
process holds the chip.
"""

from __future__ import annotations

import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _int8(w, axis=None):
    """An array rounded to an int8 grid: one scale a tensor, or one a row
    along ``axis``."""
    import jax.numpy as jnp
    w32 = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w32), axis=axis,
                            keepdims=axis is not None), 1e-30) / 127.0
    return (jnp.round(w32 / s).clip(-127, 127) * s).astype(w.dtype)


def int8_weights(params):
    """Every matrix of the model (attention, experts, shared expert,
    embedding, head) on a per-tensor int8 grid; norms, router and selection
    bias as they are."""
    import jax
    keep = ("norm", "router", "bias")
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w if any(k in jax.tree_util.keystr(path)
                                 for k in keep) else _int8(w), params)


def int8_pool(fam):
    """The family with ONE fault in its programs: after every call the
    latent pool is rounded to an int8 grid, one scale a row (a token and
    layer), so every later call reads a cache held in a lower precision
    than the configuration states."""
    def paged_programs(cfg, page, backend):
        init, prefill, chunk, decode = fam.paged_programs(cfg, page, backend)

        def held(out):
            return out[:1] + ({**out[1], "k": _int8(out[1]["k"], -1)},) \
                + out[2:]
        return (init, lambda *a: held(prefill(*a)),
                lambda *a: held(chunk(*a)), lambda *a: held(decode(*a)))
    shim = types.SimpleNamespace(**{k: getattr(fam, k) for k in dir(fam)
                                    if not k.startswith("_")})
    shim.paged_programs = paged_programs
    shim.__file__ = fam.__file__
    return shim


def controls(sz: dict) -> dict:
    """The reference's overrides, by the configuration's own widths."""
    return {
        "scale_nope": {"scale_dim": sz["nope_dim"]},
        "scale_latent": {"scale_dim": sz["latent_dim"]},
        "k_unrotated": {"rotate_k": False},
        "no_kv_norm": {"kv_norm": False},
        "no_q_norm": {"q_norm": False},
        "values_from_rope_lanes": {"value_from": sz["rope_dim"]},
        "no_shared_expert": {"shared": False},
        "no_scaling": {"scaling": 1.0},
        "no_selection_bias": {"use_bias": False}}


def brief(res: dict) -> dict:
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
            "errors": {k: e["max_abs_err"] for k, e in res["errors"].items()},
            **{k: r[k] for k in (
                "decisions", "flipped", "flip_share", "max_slack")}}


def main(argv: list[str]) -> int:
    import jax

    from benchmark import checks, common
    from ray_tpu.core import compile_cache
    compile_cache.configure()
    sound = "--controls-only" not in argv
    rehearsal = "--rehearsal" in argv       # tiny preset on the CPU
    n_controls = int(argv[argv.index("--controls") + 1]) \
        if "--controls" in argv else 1
    seeds = [int(a) for a in argv if a.isdigit() and int(a) > 1000] \
        or [4400200001]
    config = common.load_cell("joyai-llm-flash-serve-decode")[2]
    fam = common.family(config)
    sz = fam.sizes(config, rehearsal)
    eng = common.section(config, "engine", rehearsal)
    chk = common.section(config, "checks", rehearsal)["logits"]
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "limits": {k: v for k, v in chk.items() if not k.endswith("why")},
           "seeds": []}
    path = os.path.join(ROOT, "chiprun_out", "pr44", "joyai_at_size.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    for i, seed in enumerate(seeds):
        row = {"seed": seed}
        if sound:
            row.update(brief(checks.logits_check(fam, sz, eng, chk, seed)))
        row["int8_weights"] = brief(checks.logits_check(
            fam, sz, eng, chk, seed, mutate=int8_weights))
        row["int8_pool"] = brief(checks.logits_check(
            int8_pool(fam), sz, eng, chk, seed))
        if i < n_controls:
            for name, kw in controls(sz).items():
                row[name] = brief(checks.logits_check(
                    fam, sz, eng, chk, seed, **kw))
        out["seeds"].append(row)
        print(json.dumps(row), flush=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
