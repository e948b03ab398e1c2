"""What decides ``correct``. Outputs only, and the same function of code
and seed everywhere.

1. logits_check: the engine's own paged programs (whole prefill, chunked
   prefill, then decode through the cache) against the float32 reference
   of the configuration's model family (benchmark/models/<family>.py),
   at the published widths and a shallow depth, weights and tokens from the
   seed. Max absolute logit error within the configuration's tolerance.
   The programs come through the adapter (``paged_programs``). For a
   family with routed experts (its adapter has ``routing_taken``) the
   programs' choice of experts is forced on the reference and itself held
   to the reference's scores, and the root-mean-square logit error is
   held beside the maximum (benchmark/reference/__init__.py says why).
2. served_tokens_check: tokens the replica served, teacher-forced through
   the float32 reference at the served depth with the served weights.
   Every served token's reference logit lies within the configuration's
   margin of the reference maximum at its position. Never one greedy run
   against another. (A routed family's tokens go through the reference's
   own routing: the margin is wider and guards tokens, not precision.)
3. structure_check: every completed stream delivered its max_tokens or
   fewer (the engine swallows the stop token, so fewer means it stopped);
   no prompt was cut (the server counted the tokens the client sent);
   every token id inside the vocabulary; nothing NaN.
(4. the device and the compiled kernel: a run without them exits non-zero;
   see ``require_device``, and ``stated_backend`` for which backend the
   configuration's file holds check 1 of a chip run to.)

Run as a script this is the child that holds the chip after the replica
has gone: ``python benchmark/checks.py <spec.json>`` prints one JSON line.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common  # noqa: E402


def require_device(chips: int, rehearsal: bool) -> dict:
    """The device as jax reports it; SystemExit(3) unless it is ``chips``
    TPUs (a rehearsal takes what there is and says so)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearsal and (info["platform"] != "tpu" or len(devs) < chips):
        print(f"benchmark: jax found {info}, the cell needs {chips} TPU "
              f"chip(s)", file=sys.stderr)
        raise SystemExit(3)
    return info


# ---- 1. logits ---------------------------------------------------------------

def logits_check(fam, sz: dict, engine: dict, spec: dict, seed: int,
                 mutate=None, **reference_override) -> dict:
    """``fam`` is the family's adapter (``common.family``); the programs
    are the ones its ``paged_programs`` hands out, the cache whatever
    pytree they keep. ``mutate(params) -> params`` and
    ``reference_override`` are the negative controls' hooks (tests): the
    ENGINE side runs the mutated weights while the reference keeps the
    originals; an override (the dense block's ``use_rope=False``) compares
    the engine with a reference that leaves part of the mathematics out.

    A family whose adapter has ``routing_taken`` has its programs' choice
    of experts read after every call and forced on the reference, whose
    combine weights stay its own: the logits are then a continuous
    function of the arithmetic again and ``tolerance`` can be as tight as
    a dense family's. The choice itself is held to the reference's scores
    (``routing_slack``: how far below the reference's k-th best the worst
    chosen expert lies) by two limits of the configuration's file, and
    the root-mean-square error over the compared logits to
    ``rms_tolerance``: steady from seed to seed where a maximum over a
    million logits swings, so it is the number that a lower precision of
    the experts has to fail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref_mod = common.reference(fam)
    taken = getattr(fam, "routing_taken", None)   # a family that routes
    cfg = fam.model_config(sz, n_layers=spec["depth"])
    page, cap = engine["page_size"], engine["max_prompt_len"]
    chunk = engine["prefill_chunk"]
    max_pages = -(-engine["max_seq_len"] // page)
    backend = fam.attention_backend(
        engine.get("attention_kernel", "auto"), cfg, page)
    key = common.fold_seed(seed)
    k_w, k_t = jax.random.split(key)
    params = fam.init_params(k_w, cfg)
    served = mutate(params) if mutate else params
    pa, pb, d = (spec["whole_prompt_tokens"], spec["chunked_prompt_tokens"],
                 spec["decode_steps"])
    if not (pa <= chunk < pb <= cap):
        raise ValueError("logits check needs whole <= prefill_chunk < chunked")
    toks = np.asarray(jax.random.randint(
        k_t, (2, max(pa, pb) + d), 0, cfg.vocab_size), np.int32)
    seq_a, seq_b = toks[0, :pa + d], toks[1, :pb + d]

    init_cache, prefill, chunk_fn, decode = fam.paged_programs(
        cfg, page, backend)
    kv = init_cache(2 * max_pages + 1)
    tables = np.zeros((4, max_pages), np.int32)
    tables[0] = 1 + np.arange(max_pages)
    tables[1] = 1 + max_pages + np.arange(max_pages)

    def padded(seg, width):
        out = np.zeros((1, width), np.int32)
        out[0, :len(seg)] = seg
        return jnp.asarray(out)

    # a routed family: the experts each call chose, [L_r, rows, k] a call,
    # laid end to end along each sequence's positions
    choice_a, choice_b = [], []

    def chose(into, lo, hi):
        """The experts rows lo..hi of the last call took, [L_r, rows, k]."""
        if taken is not None:
            into.append(np.asarray(taken(kv))[:, lo:hi])

    t0 = time.perf_counter()
    got_a, got_b = [], []
    lg, kv = prefill(served, kv, jnp.asarray(tables[0]),
                     padded(seq_a[:pa], common.prefill_bucket(pa, cap)),
                     jnp.int32(pa))
    got_a.append(lg)
    chose(choice_a, 0, pa)
    start = 0
    while pb - start > chunk:
        _, kv = chunk_fn(served, kv, jnp.asarray(tables[1]),
                         padded(seq_b[start:start + chunk], chunk),
                         jnp.int32(start), jnp.int32(pb))
        chose(choice_b, 0, chunk)
        start += chunk
    lg, kv = chunk_fn(served, kv, jnp.asarray(tables[1]),
                      padded(seq_b[start:pb],
                             common.prefill_bucket(pb - start, cap)),
                      jnp.int32(start), jnp.int32(pb))
    got_b.append(lg)
    chose(choice_b, 0, pb - start)
    lens = jnp.asarray([pa, pb, 0, 0], jnp.int32)
    for i in range(d):
        cur = jnp.asarray([seq_a[pa + i], seq_b[pb + i], 0, 0], jnp.int32)
        lg, kv, lens = decode(served, kv, jnp.asarray(tables), lens, cur)
        got_a.append(lg[0])
        got_b.append(lg[1])
        chose(choice_a, 0, 1)
        chose(choice_b, 1, 2)
    got_a, got_b = jnp.stack(got_a), jnp.stack(got_b)
    jax.block_until_ready(got_b)
    program_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ref = fam.reference_kwargs(cfg, **reference_override)
    errs, slack, squares = {}, [], []
    for name, seq, n, got, choice in (
            ("whole_prefill+decode", seq_a, pa, got_a, choice_a),
            ("chunked_prefill+decode", seq_b, pb, got_b, choice_b)):
        forced = {}
        if taken is not None:
            forced["routing"] = np.concatenate(choice, axis=1)
            slack.append(np.asarray(ref_mod.routing_slack(
                params, seq[None], forced["routing"], **ref)))
        want = ref_mod.logits_at(params, seq[None], np.arange(n - 1, n + d),
                                 **ref, **forced)[0]
        diff = jnp.abs(got.astype(jnp.float32) - want)
        if taken is not None:
            squares.append(float(jnp.mean(diff * diff)))
        errs[name] = {
            "max_abs_err": float(jnp.max(diff)),
            "prefill_max_abs_err": float(jnp.max(diff[0])),
            "ref_max_abs": float(jnp.max(jnp.abs(want))),
            "finite": bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))}
    worst = max(e["max_abs_err"] for e in errs.values())
    finite = all(e["finite"] for e in errs.values())
    out = {"ok": bool(finite and worst <= spec["tolerance"]),
           "max_abs_err": worst, "tolerance": spec["tolerance"],
           "depth": spec["depth"], "backend": backend, "errors": errs}
    if taken is not None:
        out["rms_err"] = math.sqrt(sum(squares) / len(squares))
        out["rms_tolerance"] = spec["rms_tolerance"]
        out["routing"] = routing_verdict(
            np.concatenate([s.ravel() for s in slack]),
            spec["routing_slack"], spec["routing_flip_share_max"])
        out["ok"] = bool(out["ok"] and out["routing"]["ok"]
                         and out["rms_err"] <= out["rms_tolerance"])
    out.update(program_s=program_s, reference_s=time.perf_counter() - t0)
    return out


def routing_verdict(slack, slack_limit: float, flip_share_max: float) -> dict:
    """``slack``: one number a routing decision (a token in a layer that
    routes), the reference's k-th largest selection score minus the
    smallest among the experts the program chose: 0 where the program
    chose the reference's top k, ``inf`` for an expert named twice or not
    there. A decision with slack above 0 has turned over. Sound programs
    turn a few near-ties over, by no more than their arithmetic's noise in
    a score; a wrong rule turns many, or one by much."""
    import numpy as np
    slack = np.asarray(slack, np.float64)
    flipped = int(np.sum(slack > 0))
    worst = float(np.max(slack)) if slack.size else float("inf")
    share = flipped / slack.size if slack.size else 1.0
    return {"ok": bool(math.isfinite(worst) and worst <= slack_limit
                       and share <= flip_share_max),
            "decisions": int(slack.size), "flipped": flipped,
            "flip_share": share, "max_slack": worst,
            "slack_limit": slack_limit, "flip_share_max": flip_share_max}


# ---- 2. served tokens, teacher-forced ---------------------------------------------

def served_tokens_check(ref_mod, ref: dict, params, samples: list[dict],
                        margin: float, *, eos: int | None,
                        width: int | None = None,
                        out_width: int | None = None) -> dict:
    """``ref_mod`` is the family's reference module and ``ref`` the
    keywords it takes (``fam.reference_kwargs(cfg)``).
    samples: [{"prompt_ids", "tokens", "max_tokens"}]. For each served
    token at position t: reference max logit at t-1 minus the reference
    logit of the served token. A stream that stopped short is checked on
    the stop token it must have produced. ``width`` / ``out_width`` fix
    the padded shapes (prompt + output, output + 1) so that every run of a
    cell compiles the same reference programs."""
    import numpy as np

    if not samples:
        return {"ok": False, "reason": "no served sample to check"}
    seqs, spans = [], []
    for s in samples:
        out = list(s["tokens"])
        if len(out) < s["max_tokens"] and eos is not None:
            out = out + [eos]          # swallowed by the engine, but produced
        seqs.append(list(s["prompt_ids"]) + out)
        spans.append((len(s["prompt_ids"]), len(out)))
    out_width = max([out_width or 0] + [n for _, n in spans])
    width = max([width or 0] + [len(q) for q in seqs])
    width = -(-(width + out_width) // 64) * 64   # room for the padded slice
    toks = np.zeros((len(seqs), width), np.int32)
    for i, q in enumerate(seqs):
        toks[i, :len(q)] = q
    hidden = ref_mod.hidden(params, toks, **ref)
    per_sample, worst, finite = [], 0.0, True
    for i, (plen, n) in enumerate(spans):
        if n == 0:
            per_sample.append(0.0)
            continue
        served = np.zeros((out_width,), np.int32)
        served[:n] = seqs[i][plen: plen + n]
        deficit, ok = ref_mod.deficits(params, hidden[i], plen - 1, served, n,
                                       **ref)
        finite &= bool(ok)
        per_sample.append(float(deficit))
        worst = max(worst, per_sample[-1])
    return {"ok": bool(finite and worst <= margin), "max_deficit": worst,
            "margin": margin, "per_sample_max_deficit": per_sample,
            "tokens_checked": sum(n for _, n in spans), "finite": finite}


# ---- 3. structure ----------------------------------------------------------------

def structure_check(records: list[dict], samples: list[dict],
                    vocab_size: int) -> dict:
    """records: the window's completed streams (client side: counts only —
    the proxy keeps token ids to itself); samples: served requests with
    their token ids."""
    bad = []
    early = 0
    for r in records:
        n, cap = r.get("completion_tokens", 0), r["max_tokens"]
        if not 0 <= n <= cap:
            bad.append(f"request {r['index']}: {n} tokens of {cap}")
        early += int(n < cap)
        # the engine cuts a prompt above its max_prompt_len silently: the
        # server's count of prompt tokens has to be the client's
        asked, got = r.get("prompt_tokens_asked"), r.get("prompt_tokens")
        if asked is not None and got is not None and got != asked:
            bad.append(f"request {r['index']}: prompt of {asked} tokens "
                       f"served as {got}")
    for i, s in enumerate(samples):
        toks = s["tokens"]
        if len(toks) > s["max_tokens"]:
            bad.append(f"sample {i}: {len(toks)} tokens of {s['max_tokens']}")
        if any((not isinstance(t, int)) or t < 0 or t >= vocab_size
               for t in toks):
            bad.append(f"sample {i}: token outside the vocabulary")
    return {"ok": not bad, "problems": bad[:5], "streams": len(records),
            "stopped_early": early}


def train_structure_check(losses: list[float], first_ref: float,
                          tolerance: float, last_same_batch: float) -> dict:
    """Every loss finite; the first step's loss equal to the reference's on
    the same batch and initial parameters; the last loss on that same
    batch (the data is a cycle) below the first."""
    finite = all(isinstance(x, float) and math.isfinite(x) for x in losses)
    err = abs(losses[0] - first_ref) if losses else float("inf")
    falls = len(losses) >= 2 and last_same_batch < losses[0]
    return {"ok": bool(finite and err <= tolerance and falls),
            "finite": finite, "first_loss": losses[0] if losses else None,
            "reference_first_loss": first_ref, "abs_err": err,
            "tolerance": tolerance, "last_loss_same_batch": last_same_batch,
            "falls": falls}


# ---- the child ---------------------------------------------------------------------

def stated_backend(logits_spec: dict) -> str:
    """The attention backend check 1 of a chip run must resolve to:
    ``checks.logits.backend`` of the configuration's file, "pallas" where
    the file says nothing. A file may state "gather" (a block the Pallas
    kernel cannot tile: heads of 64, a latent cache) only with its reason.
    The gate on the REPLICA (serve_cell) is not this one: the engine
    serves through the compiled kernel or not at all."""
    want = logits_spec.get("backend", "pallas")
    if want not in ("pallas", "gather"):
        raise common.BenchError(
            f"checks.logits.backend is {want!r}: 'pallas' or 'gather'")
    if want == "gather" and not logits_spec.get("backend_why"):
        raise common.BenchError("checks.logits.backend is 'gather' and the "
                                "file gives no backend_why")
    return want


def serve_child(spec: dict) -> dict:
    """Checks 1 and 2 of a serve cell in one process that holds the chip
    (after the replica released it)."""
    import jax

    from ray_tpu.ops import paged_attention as paged_ops

    rehearsal = spec["rehearsal"]
    fam = common.load_module("models", spec["family"])
    device = require_device(spec["chips"], rehearsal)
    sz, engine = spec["sizes"], spec["engine"]
    out = {"device": device, "interpret": bool(paged_ops.interpret_default())}
    t0 = time.perf_counter()
    stated = stated_backend(spec["checks"]["logits"])
    out["logits"] = logits_check(fam, sz, engine, spec["checks"]["logits"],
                                 spec["seed"])
    if not rehearsal and (out["logits"]["backend"] != stated
                          or out["interpret"]):
        print(f"benchmark: attention backend {out['logits']['backend']!r}, "
              f"interpret={out['interpret']}: not the compiled "
              f"{stated!r} the configuration's file states",
              file=sys.stderr)
        raise SystemExit(3)
    out["logits_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    cfg = fam.model_config(sz)
    # the served weights: LLMEngine(cfg) -> init_params(PRNGKey(0), model)
    params = jax.block_until_ready(
        fam.init_params(jax.random.PRNGKey(0), cfg))
    out["served_weights_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    out["served_tokens"] = served_tokens_check(
        common.reference(fam), fam.reference_kwargs(cfg), params,
        spec["samples"], spec["checks"]["served_tokens"]["margin"],
        eos=common.BYTE_EOS, **spec.get("shape", {}))
    out["served_tokens_s"] = time.perf_counter() - t0
    return out


def main(argv: list[str]) -> int:
    from ray_tpu.core import compile_cache
    compile_cache.configure()
    with open(argv[1]) as f:
        spec = json.load(f)
    out = serve_child(spec)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
