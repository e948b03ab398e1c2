"""Check 2's negative control for ``sdar-30b-a3b-chat-serve-1chip`` at the depth
it is served at, on the chip: what its ``margin`` is read against.

    chiprun -- python3 tests/benchmark_suite/sdar_planted_token.py <spec> ...

``<spec>`` is a ``checks_spec.json`` that a run of the cell left in
``.bench_out/<cell>/``: the four greedy requests the replica served beside
the window's load, with the limits and shapes the checks child was given.
The served weights are rebuilt as the child rebuilds them, the samples go
through ``checks.served_tokens_check`` as served (the sound reading), and
then with ONE token of every sample replaced by another (``+ 7`` modulo
the vocabulary, the toy's control): the second token served, the middle
one, the last one. A replaced token also changes what every later
position is conditioned on (and, in its own block, the other positions
revealed after it), so the earlier it sits the more positions miss; the
LAST one sits in the block that ``max_tokens`` cut. ``ok`` is check 2's own verdict under the committed margin, and so
what ``correct`` would have read. Written to
``chiprun_out/sdar_planted_token.json``. This process holds the chip.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PLANTS = {"second": lambda n: 1, "middle": lambda n: n // 2,
          "last": lambda n: n - 1}


def main(argv: list[str]) -> int:
    import jax

    from benchmark import checks, common
    from ray_tpu.core import compile_cache
    compile_cache.configure()
    out = {"runs": []}
    path = os.path.join(ROOT, "chiprun_out", "sdar_planted_token.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    params = None
    for spec_path in argv:
        with open(spec_path) as f:
            spec = json.load(f)
        fam = common.load_module("models", spec["family"])
        cfg = fam.model_config(spec["sizes"])
        if params is None:          # the served weights: one seed for all
            params = jax.block_until_ready(
                fam.init_params(jax.random.PRNGKey(0), cfg))
        margin = spec["checks"]["served_tokens"]["margin"]

        def check(samples):
            res = checks.served_tokens_check(
                common.reference(fam), fam.reference_kwargs(cfg), params,
                samples, margin, eos=common.BYTE_EOS, **spec.get("shape", {}))
            return {"ok": res["ok"], "max_deficit": res["max_deficit"],
                    "per_sample_max_deficit": res["per_sample_max_deficit"]}

        row = {"spec": spec_path, "seed": spec["seed"], "margin": margin,
               "depth": cfg.n_layers, "as_served": check(spec["samples"])}
        for name, at in PLANTS.items():
            bad = [dict(s, tokens=list(s["tokens"])) for s in spec["samples"]]
            for s in bad:
                i = at(len(s["tokens"]))
                s["tokens"][i] = (s["tokens"][i] + 7) % cfg.vocab_size
            row["replaced_" + name] = check(bad)
        out["runs"].append(row)
        print(json.dumps(row), flush=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
