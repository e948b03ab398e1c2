"""Check 1 of ``mimo-v2-flash-serve-1chip`` at the published widths, on the
chip, seed after seed: what its four limits are read from.

    chiprun -- python3 tests/benchmark_suite/mimo_at_size.py <seed> ...

Per seed the routing-aware comparison as the cell runs it, under the limits
the configuration's file commits (``ok`` is check 1's own verdict,
``failed_by`` the limits that refused it), and the same with matrices of
the model on a per-tensor int8 grid (the nearest precision below the
configuration's bfloat16: it has to come out as not correct). NOT every
matrix: check 1 holds the served weights beside the reference's, 8.05 GB at
depth 6, so the grid takes what fits beside them (:func:`int8_weights`).
On the first ``--controls N`` seeds (default 1) also the reference with one
rule left out or wrong (benchmark/reference/mimo_v2_flash_f32.py lists
them): the window one token short or long, the sink left out of the window
layers, a sink added to the full layers, the value scale left out, every
lane rotated, the two thetas swapped, the selection bias left out; and the
PROGRAMS with a ring one page short, three pages short and with
neighbouring pages on one page (:func:`short_ring`: which of them loses a
live token depends on the backend and on where the prompt ends). ``--controls-only`` leaves the sound comparison out;
``--rehearsal`` walks the script at the tiny preset on the CPU (no device
number comes of it). Written to chiprun_out/pr55/mimo_at_size.json. This
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

CELL = "mimo-v2-flash-serve-reasoning"
# the logit a control gives every head of the full layers: the seeded
# sinks' mean (ray_tpu/models/mimo.SINK_MEAN)
FULL_SINK = 4.0


def _int8(w):
    """An array rounded to an int8 grid, one scale a tensor."""
    import jax.numpy as jnp
    w32 = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w32)), 1e-30) / 127.0
    return (jnp.round(w32 / s).clip(-127, 127) * s).astype(w.dtype)


def int8_weights(params):
    """The attention matrices (q, k, v, out) of every layer, the dense
    layer's SwiGLU and the FIRST routed layer's held experts on a
    per-tensor int8 grid (2.3 GB of the 8.05 at depth 6: a second copy of
    all of it does not fit the chip beside the first); norms, sinks,
    routers, selection biases, the other layers' experts, embedding and
    head as they are (the same arrays, no copy)."""
    import jax
    first_routed = next(i for i, lp in enumerate(params["layers"])
                        if "moe" in lp)

    def grid(path, w):
        at = jax.tree_util.keystr(path)
        if any(k in at for k in ("norm", "sink", "router", "bias", "embed",
                                 "lm_head")):
            return w
        if "'moe'" in at and f"[{first_routed}]" not in at:
            return w
        return _int8(w)

    return jax.tree_util.tree_map_with_path(grid, params)


def short_ring(fam, short: int = 1):
    """The family with ONE fault in its programs: a ring that holds fewer
    pages than it walks. ``short`` 1 or 3: the last ``short`` entries of
    every sequence's ring table name the pages of its first ``short``
    (Trinity's control: one page short, and a ring of 3 where a prefill
    chunk alone writes 4). ``short`` 0, "PAIRS": every odd entry names the
    page of the even entry before it, so logical pages 2i and 2i + 1 share
    a page (a ring of 3, neighbours on one page).

    What refuses which follows from WHERE A CALL ENDS (my chip runs, PR 55;
    PERF.md section 6). With a window of exactly ONE page and the call's
    rows written INSIDE the walking kernel (which reads a slot's pages into
    its scratch, a logical page a place, before it writes a tile back),
    position p on the row of p - 128 x n harms nothing: that token left
    the window as p arrived. A live token is lost only where ONE call
    writes two NEIGHBOURING logical pages that share a page and ends inside
    the later one: the tile that holds the call's last row is written back
    whole, and its rows past the end are the bytes the kernel read BEFORE
    the call wrote the earlier page's rows to the same place, so the
    earlier page's rows at those offsets, the oldest keys of the next decode
    steps' window, are gone. Chunks start on whole pages, so only a
    prompt's END lies inside a page. A ring ONE page short puts logical
    pages 6 m + 5 and 6 m + 6 on one page: a prompt that ends in page 6 m +
    6 with page 6 m + 5 in the same chunk of four (m even: the committed
    3,959 = page 30, offset 119) is refused; one that ends in page 23
    (3,066, the first hand-in's length) reads the sound values to the last
    bit. PAIRS puts 2 i and 2 i + 1 on one page: refused where the prompt
    ends in an odd page (3,066), sound where in an even one (3,959). THREE
    short never puts neighbours on one page: sound at any length. The
    gather backend and a whole prefill scatter before they read, and every
    variant is refused there."""
    import jax.numpy as jnp

    def with_rings(tables, full_w, ring):
        out = fam.with_rings(tables, full_w, ring)
        if not short:
            first = out[..., full_w::2]
            return jnp.concatenate(
                [out[..., :full_w],
                 jnp.repeat(first, 2, axis=-1)[..., :ring]], axis=-1)
        return jnp.concatenate(
            [out[..., :-short], out[..., full_w:full_w + short]], axis=-1)

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
        "no_sink": {"window_sink": False},
        "sink_in_full_layers": {"full_sink": FULL_SINK},
        "no_value_scale": {"value_scale": 1.0},
        "every_lane_rotated": {"rotary": sz["head_dim"]},
        "thetas_swapped": {"theta_full": sz["window_rope_theta"],
                           "theta_window": sz["rope_theta"]},
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
        or [5500200001]
    config = common.load_cell(CELL)[2]
    fam = common.family(config)
    sz = fam.sizes(config, rehearsal)
    eng = common.section(config, "engine", rehearsal)
    chk = common.section(config, "checks", rehearsal)["logits"]
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "limits": {k: v for k, v in chk.items() if not k.endswith("why")},
           "seeds": []}
    path = os.path.join(ROOT, "chiprun_out", "pr55", "mimo_at_size.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    for i, seed in enumerate(seeds):
        row = {"seed": seed}
        if sound:
            row.update(brief(checks.logits_check(fam, sz, eng, chk, seed)))
        row["int8_weights"] = brief(checks.logits_check(
            fam, sz, eng, chk, seed, mutate=int8_weights))
        if i < n_controls:
            for name, short in (("short_ring_1", 1), ("short_ring_3", 3),
                                ("short_ring_pairs", 0)):
                row[name] = brief(checks.logits_check(
                    short_ring(fam, short), sz, eng, chk, seed))
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
