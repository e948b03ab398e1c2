"""Shared arithmetic of the traffic generators: one fixed schedule for
every seed, entered at a point the seed chooses.

A run's work must not depend on the seed's luck (a seed that happens to
draw three 1024-token prompts is a different workload), so lengths and
inter-arrival gaps are the stratified quantiles of their distribution
(quantile (i + 0.5) / n for i < n), put ONCE into an order drawn from
``schedule_seed`` in the cell's file. The run's seed chooses where in
that cycle its window starts, and the prompt text. So a cell replays one
schedule; it does not draw arrivals afresh. Which long prompts arrive
close together decides a tail: a free permutation by the seed made the
p90s of six seeds spread by 5-7 % while two runs of one seed agreed to
0.2 % (my chip runs, PR 24).
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

_NORMAL = NormalDist()


def lognormal_lengths(spec: dict, n: int) -> list[int]:
    """n stratified quantiles of a log-normal with the given median and
    sigma, clamped to [min, max]; sorted ascending."""
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        v = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(spec["max"], max(spec["min"], round(v)))))
    return out


def exponential_gaps(rate_per_s: float, n: int, total_s: float) -> list[float]:
    """n stratified quantiles of Exp(rate), scaled so they sum to a little
    under total_s (every arrival falls due inside the window)."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate_per_s for i in range(n)]
    scale = total_s * n / (n + 0.5) / sum(gaps)
    return [g * scale for g in gaps]


def prompt_text(rng: random.Random, n_tokens: int) -> str:
    """Printable ASCII that the byte tokenizer turns into n_tokens ids
    (BOS + one id per character)."""
    return "".join(chr(rng.randrange(32, 127))
                   for _ in range(max(1, n_tokens - 1)))


def rotated(values: list, seed: int) -> list:
    """``values`` from the seed's starting point in their cycle."""
    k = random.Random(seed).randrange(len(values))
    return values[k:] + values[:k]


def ordered(values: list, params: dict, seed: int, salt: int) -> list:
    """``values`` in the file's fixed order (``schedule_seed``; ``salt``
    tells lengths from gaps), rotated to the seed's starting point."""
    values = list(values)
    random.Random(int(params["schedule_seed"]) * 7919 + salt).shuffle(values)
    return rotated(values, seed)


def dealt_lengths(spec: dict, n: int, params: dict, salt: int) -> list[int]:
    """n lengths in the file's fixed order, before the seed's rotation.
    With ``deal_block`` B in the file they are dealt in blocks: every run
    of B requests from a multiple of B on holds the B stratified quantiles
    once, in an order of its own, so any stretch of the cycle far longer
    than B holds nearly the same lengths wherever the seed enters it.
    Without it: ONE shuffle of the n quantiles (``ordered``'s, to the
    letter), and a loop that draws a part of its pool reads by which part
    (the cell file's ``deal_why`` has the readings)."""
    block = int(params.get("deal_block") or n)
    if block < 1:
        raise ValueError(f"deal_block must be a positive count, not {block}")
    rng = random.Random(int(params["schedule_seed"]) * 7919 + salt)
    out: list[int] = []
    while len(out) < n:
        part = lognormal_lengths(spec, min(block, n - len(out)))
        rng.shuffle(part)
        out += part
    return out


def requests_for(params: dict, seed: int, n: int) -> list[dict]:
    """n requests: stratified prompt and output lengths in the schedule's
    order from the seed's starting point, prompt text from the seed."""
    rng = random.Random(seed)
    plens = rotated(dealt_lengths(params["prompt_tokens"], n, params, 1), seed)
    olens = rotated(dealt_lengths(params["output_tokens"], n, params, 2), seed)
    return [{"index": i, "prompt_tokens": p, "max_tokens": o,
             "prompt": prompt_text(rng, p)}
            for i, (p, o) in enumerate(zip(plens, olens))]
