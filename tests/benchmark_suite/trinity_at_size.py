"""Check 1 of ``trinity-large-preview-serve-1chip`` at the published widths,
on the chip, seed after seed: what its four limits are read from.

    chiprun -- python3 tests/benchmark_suite/trinity_at_size.py <seed> ...

Per seed the routing-aware comparison as the cell runs it, under the limits
the configuration's file commits (``ok`` is check 1's own verdict,
``failed_by`` the limits that refused it), and the same with matrices of
the model on a per-tensor int8 grid (the nearest precision below the
configuration's bfloat16: it has to come out as not correct). NOT every
matrix: check 1 holds the served weights beside the reference's, 8.8 GB at
depth 4, so the grid takes what fits beside them (:func:`int8_weights`).
On the first ``--controls N`` seeds (default 1) also the reference with one
rule left out or wrong (benchmark/reference/afmoe_f32.py lists them): the
window one token short or long, rotation in the full layer, none in the
window layers, the gate, ``n2``, ``n4``, the embedding's ``sqrt(hidden)``,
the shared expert or the selection bias left out; and the PROGRAMS with a
ring one page short (:func:`short_ring`: a live token overwritten).
``--controls-only`` leaves the sound comparison out; ``--rehearsal`` walks
the script at the tiny preset on the CPU (no device number comes of it).
Written to chiprun_out/pr52/trinity_at_size.json. This process holds the
chip.
"""

from __future__ import annotations

import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "trinity-large-serve-longctx"


def _int8(w):
    """An array rounded to an int8 grid, one scale a tensor."""
    import jax.numpy as jnp
    w32 = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w32)), 1e-30) / 127.0
    return (jnp.round(w32 / s).clip(-127, 127) * s).astype(w.dtype)


def int8_weights(params):
    """The attention matrices (q, k, v, gate, out) of every layer, the
    dense layer's SwiGLU, every shared expert and the FIRST routed layer's
    held experts on a per-tensor int8 grid (2.7 GB of the 8.8 at depth 4:
    a second copy of all of it does not fit the chip beside the first);
    norms, routers, selection biases, the other layers' experts, embedding
    and head as they are (the same arrays, no copy)."""
    import jax
    first_routed = next(i for i, lp in enumerate(params["layers"])
                        if "moe" in lp)

    def grid(path, w):
        at = jax.tree_util.keystr(path)
        if any(k in at for k in ("norm", "router", "bias", "embed",
                                 "lm_head")):
            return w
        if "'moe'" in at and "'shared'" not in at \
                and f"[{first_routed}]" not in at:
            return w
        return _int8(w)

    return jax.tree_util.tree_map_with_path(grid, params)


def short_ring(fam):
    """The family with ONE fault in its programs: the last entry of every
    sequence's ring table names the page of its first, so the ring holds
    one page less than it walks and a page still inside the window is
    overwritten."""
    import jax.numpy as jnp

    def with_rings(tables, full_w, ring):
        out = fam.with_rings(tables, full_w, ring)
        return jnp.concatenate(
            [out[..., :-1], out[..., full_w:full_w + 1]], axis=-1)

    shim = types.SimpleNamespace(**{k: getattr(fam, k) for k in dir(fam)
                                    if not k.startswith("_")})
    shim.paged_programs = lambda cfg, page, backend: fam.build_programs(
        cfg, page, backend, with_rings)
    shim.__file__ = fam.__file__
    return shim


def controls(sz: dict) -> dict:
    """The reference's overrides, by the configuration's own widths."""
    return {
        "window_short": {"window": sz["window"] - 1},
        "window_long": {"window": sz["window"] + 1},
        "full_rotated": {"rotate_full": True},
        "window_unrotated": {"rotate_window": False},
        "no_gate": {"gate": False},
        "no_n2": {"post_attn_norm": False},
        "no_n4": {"post_ffn_norm": False},
        "no_mup": {"mup": False},
        "no_shared_expert": {"shared": False},
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
            "program_s": res["program_s"], "reference_s": res["reference_s"],
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
        or [5200200001]
    config = common.load_cell(CELL)[2]
    fam = common.family(config)
    sz = fam.sizes(config, rehearsal)
    eng = common.section(config, "engine", rehearsal)
    chk = common.section(config, "checks", rehearsal)["logits"]
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "limits": {k: v for k, v in chk.items() if not k.endswith("why")},
           "seeds": []}
    path = os.path.join(ROOT, "chiprun_out", "pr52", "trinity_at_size.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    for i, seed in enumerate(seeds):
        row = {"seed": seed}
        if sound:
            row.update(brief(checks.logits_check(fam, sz, eng, chk, seed)))
        row["int8_weights"] = brief(checks.logits_check(
            fam, sz, eng, chk, seed, mutate=int8_weights))
        if i < n_controls:
            row["short_ring"] = brief(checks.logits_check(
                short_ring(fam), sz, eng, chk, seed))
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
