"""Check 1 of ``falcon-h1-34b-instruct-serve-1chip`` at the published widths,
on the chip, seed after seed: what its one limit is read from.

    chiprun -- python3 tests/benchmark_suite/falcon_h1_at_size.py <seed> ...

Per seed the comparison as the cell runs it, under the limit the
configuration's file commits (``ok`` is check 1's own verdict), and on the
first ``--controls N`` seeds (default 1) every negative control of ISSUE
60: the REFERENCE with one rule left out or wrong (:func:`controls`: a
branch left out, a multiplier at 1, the skip, dt's bias, the gate's order,
the groups, the convolution's bias, the state forgotten at every chunk's
edge) and the PROGRAMS with one fault (:func:`faulty`: padded columns
allowed to update the state, two sequences on one state row, the recurrent
state kept in bfloat16) or with every layer's matrices on a per-tensor int8
grid (the nearest precision below the configuration's bfloat16).
``--controls-only`` leaves the sound comparison out; ``--rehearsal`` walks
the script at the tiny preset on the CPU (no device number comes of it).
Written to chiprun_out/pr60/falcon_h1_at_size.json. This process holds the
chip.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "falcon-h1-34b-serve-decode"


def _int8(w):
    """An array rounded to an int8 grid, one scale a tensor."""
    import jax.numpy as jnp
    w32 = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w32)), 1e-30) / 127.0
    return (jnp.round(w32 / s).clip(-127, 127) * s).astype(w.dtype)


def int8_weights(params):
    """Every layer's matrices (the three mixers' projections) on a
    per-tensor int8 grid; norms, the convolution, the heads' constants,
    embedding and head as they are (the same arrays: a second copy of the
    2.7 GB a piece does not fit beside the reference)."""
    import jax

    def grid(path, w):
        at = jax.tree_util.keystr(path)
        if "layers" not in at or w.ndim < 2 or "conv" in at:
            return w
        return _int8(w)

    return jax.tree_util.tree_map_with_path(grid, params)


def controls(sz: dict, chunk: int) -> dict:
    """The reference's overrides."""
    m0, m1 = sz["mlp_multipliers"]
    return {
        "no_mamba": {"mamba": False},
        "no_attention": {"attention": False},
        "no_mlp": {"mlp": False},
        "ssm_out_multiplier_1": {"ssm_out_multiplier": 1.0},
        "attention_out_multiplier_1": {"attention_out_multiplier": 1.0},
        "key_multiplier_1": {"key_multiplier": 1.0},
        "ssm_multipliers_1": {"ssm_multipliers": (1.0,) * 5},
        "mlp_gate_multiplier_1": {"mlp_multipliers": (1.0, m1)},
        "mlp_down_multiplier_1": {"mlp_multipliers": (m0, 1.0)},
        "no_skip": {"skip": False},
        "no_dt_bias": {"dt_bias": False},
        "norm_before_gate": {"norm_before_gate": True},
        "one_group": {"one_group": True},
        "no_conv_bias": {"conv_bias": False},
        "state_not_carried": {"state_reset_every": chunk}}


@contextlib.contextmanager
def faulty(fam, fault: str):
    """The family's programs with ONE fault planted in the state-space
    half (serve/llm/kv_cache.py ``_ssm_half``), for the length of the
    block: ``padding_updates`` (the columns past a prompt's end update the
    state and the kept columns), ``shared_row`` (every sequence on state
    row 1), ``state_bf16`` (the recurrent state rounded to bfloat16 after
    every call that writes it)."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm import kv_cache as kvc
    sound = kvc._ssm_half

    def planted(kv, layer, cfg, ld, z, xbc, dt, rows, fresh, n_real, kernel):
        if fault == "padding_updates" and n_real is not None:
            n_real = jnp.full_like(n_real, xbc.shape[1])
        if fault == "shared_row":
            rows = jnp.ones_like(rows)
        out, kv = sound(kv, layer, cfg, ld, z, xbc, dt, rows, fresh, n_real,
                        kernel)
        if fault == "state_bf16":
            i = ld.state_layer
            taps, pool = kv["state"][i]
            pool = pool.astype(jnp.bfloat16).astype(pool.dtype)
            kv = {**kv, "state": kv["state"][:i] + ((taps, pool),)
                  + kv["state"][i + 1:]}
        return out, kv

    fam.paged_programs.cache_clear()
    with mock.patch.object(kvc, "_ssm_half", planted):
        yield fam
    fam.paged_programs.cache_clear()


FAULTS = ("padding_updates", "shared_row", "state_bf16")


def brief(res: dict) -> dict:
    return {"ok": res["ok"], "max_abs_err": res["max_abs_err"],
            "backend": res["backend"],
            "ref_max_abs": max(e["ref_max_abs"]
                               for e in res["errors"].values()),
            "errors": {k: e["max_abs_err"] for k, e in res["errors"].items()},
            "program_s": res["program_s"], "reference_s": res["reference_s"]}


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
        or [6000200001]
    config = common.load_cell(CELL)[2]
    fam = common.family(config)
    sz = fam.sizes(config, rehearsal)
    eng = common.section(config, "engine", rehearsal)
    chk = common.section(config, "checks", rehearsal)["logits"]
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "limits": {k: v for k, v in chk.items() if not k.endswith("why")},
           "seeds": []}
    path = os.path.join(ROOT, "chiprun_out", "pr60", "falcon_h1_at_size.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    for i, seed in enumerate(seeds):
        row = {"seed": seed}
        if sound:
            row.update(brief(checks.logits_check(fam, sz, eng, chk, seed)))
        if i < n_controls:
            row["int8_weights"] = brief(checks.logits_check(
                fam, sz, eng, chk, seed, mutate=int8_weights))
            for fault in FAULTS:
                with faulty(fam, fault) as planted:
                    row[fault] = brief(checks.logits_check(
                        planted, sz, eng, chk, seed))
            for name, kw in controls(fam.sizes(config, False),
                                     eng["prefill_chunk"]).items():
                row[name] = brief(checks.logits_check(
                    fam, sz, eng, chk, seed, **kw))
        out["seeds"].append(row)
        print(json.dumps(row), flush=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
