"""How far the engine loop runs ahead of the device when nothing queues.

The device runs one ordered stream, so whatever the loop has dispatched
stands between an arriving prompt's prefill and its first token: at most
``pipeline_depth`` entries of k decode steps each (engine.py ``_step``).
With callers waiting the engine picks k by the queue's state
(``_select_block``); with none waiting there is capacity to spare, and the
only reason to dispatch more than the smallest tier is a host that cannot
keep the device fed with it. This module holds that one decision: the idle
tier's k is the SMALLEST of the engine's tiers (one step, the
pressure tier's, ``decode_block``), and climbs towards ``decode_block`` only
when the loop sees the device run dry (engine.py ``_dry``) through its own
fault, again and again.

On a v5e (Mistral-7B at depth 16, 2 requests/s, widths 4-8, depth 3; my
chip runs, PR 42, PERF.md section 6; four seeds each): k = 8, the parent's,
reads ``ttft_p90_ms`` 419-482 and ``tpot_p90_ms`` 15.10-17.50; k = 2 reads
173-184 and 15.86-16.00; k = 1 reads 130-138 and 15.54-15.78 with the device
idle 0.024 % of a trace. A block of 2 saves nothing a step over single steps
there (13.35 ms against 13.08; blocks of 8: 12.49), so the ladder starts at
one step.
"""

import collections

# A climb needs CLIMB_DRY dry dispatches among the last CLIMB_WINDOW idle-
# tier dispatches. One stall of the host is ONE dry dispatch, whatever its
# length, and says nothing of the tier: the start of a profiler capture
# (0.09-0.12 s in one dispatch span), a process not scheduled for 3.45 s,
# a full collection (PERF.md section 6, PR 39: a dry dispatch each). A
# host that is too slow for the tier goes dry every few dispatches. On a
# v5e, the cell above (my chip runs, PR 42): 0-2 dry dispatches of any kind
# in 3,400-3,700 idle-tier dispatches a 51 s window at k = 1 (1-2 in
# 1,700-1,830 at k = 2), none under a collection, 0 climbs in every run;
# the tiny model of a CPU rehearsal goes dry 25 times in 92 and climbs.
CLIMB_DRY = 3
CLIMB_WINDOW = 64
# A descent needs that many idle-tier dispatches in a row without a dry
# one: 256 blocks of 8 steps of 12 ms are 24 s, so a host that was slow
# for a while is tried again a few times a minute. A climb that follows a
# descent within the same count says the descent was wrong, and doubles
# the count (up to HOLD_MAX times the base: a try every half hour).
DESCEND_CLEAN = 256
HOLD_MAX = 64


class IdleLead:
    """The idle tier's k. ``tiers``: the k the engine dispatches its
    decode program at (one step, its pressure tier, its ``decode_block``);
    ``k`` is always one of them, the smallest at first. One writer: the
    loop thread."""

    __slots__ = ("tiers", "climbs", "descents", "_i", "_n", "_dry",
                 "_clean", "_hold", "_descended_at", "_gc_n")

    def __init__(self, tiers, gc_n: int = 0):
        self.tiers = tuple(sorted(set(tiers)))
        self.climbs = 0
        self.descents = 0
        self._i = 0
        self._n = 0                     # idle-tier dispatches observed
        self._dry = collections.deque(maxlen=CLIMB_DRY)   # their numbers
        self._clean = 0                 # since the last dry one or move
        self._hold = DESCEND_CLEAN
        self._descended_at = None
        self._gc_n = gc_n

    @property
    def k(self) -> int:
        return self.tiers[self._i]

    def observe(self, dry: int, gc_n: int) -> None:
        """One idle-tier dispatch at ``k``: ``dry`` as ``_dry`` gave it
        (never 1 for the first dispatch after a ``loop_wait``), ``gc_n``
        the process's count of full collections. A dry dispatch under a
        collection is the collector's, not the tier's: it neither counts
        towards a climb nor ends a clean run."""
        collected, self._gc_n = gc_n != self._gc_n, gc_n
        if dry and collected:
            return
        self._n += 1
        if not dry:
            self._clean += 1
            if self._i and self._clean >= self._hold:
                self._move(-1)
                self.descents += 1
                self._descended_at = self._n
            return
        self._clean = 0
        self._dry.append(self._n)
        if len(self._dry) == CLIMB_DRY \
                and self._n - self._dry[0] < CLIMB_WINDOW \
                and self._i + 1 < len(self.tiers):
            if self._descended_at is not None \
                    and self._n - self._descended_at <= self._hold:
                self._hold = min(self._hold * 2, DESCEND_CLEAN * HOLD_MAX)
            self._move(+1)
            self.climbs += 1

    def _move(self, by: int) -> None:
        self._i += by
        self._dry.clear()
        self._clean = 0
