"""The one traffic generator: reads a traffic file's tables, never a distribution.

A traffic file holds ``pairs`` (a list of ``[prompt_len, answer_len]``) and, for an
open loop, ``gaps_unit`` (inter-arrival gaps with mean 1, multiplied by
``1 / rate_rps`` when sent).  Every run sends the same requests at the same times:
each pass over a table has a fixed shuffled order of its own that does NOT depend on
``--seed``, which draws only the token ids (and the weights).  Measured on the chip
(PERF.md, Findings PR 24): with the order drawn from the seed, two runs of one seed
agreed to 0.04 % in tokens/s and runs of different seeds spread by 1.2 %, so the seed
was changing the work.  A run's load has two phases: WARM traffic until the runner says the
system is in steady state, then the MAIN phase, which starts a fresh pass, so a
window as long as one pass holds each pair and each gap exactly once.  ``loop`` is
``"open"`` (independent users: send on the schedule whatever the system does) or
``"closed"`` (``callers`` callers, each sending its next item when its last one
returned).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, List, Tuple

import numpy as np


WARM, MAIN = 0, 1                  # the two phases of a run's load


def _cycle(table: list, key: list) -> Iterator:
    """The table over and over, each pass in a fixed shuffled order of its own."""
    n_pass = 0
    while True:
        order = np.random.default_rng(key + [n_pass]).permutation(len(table))
        for i in order:
            yield table[i]
        n_pass += 1


def plan(traffic: dict, phase: int, rate_rps: float = None
         ) -> Iterator[Tuple[float, int, int]]:
    """``(gap_s, prompt_len, answer_len)`` for one phase's request 0, 1, 2, ...;
    the gap is 0 in a closed loop.  A pass over the tables sends every pair and
    every gap once."""
    pairs = _cycle(traffic["pairs"], [phase, 0])
    if traffic["loop"] == "open":
        unit = traffic["gaps_unit"]
        # mean gap exactly 1 / rate, so that a pass lasts len(unit) / rate seconds
        scale = len(unit) / sum(unit) / float(rate_rps or traffic["rate_rps"])
        gaps = _cycle(unit, [phase, 1])
        for (p, a), g in zip(pairs, gaps):
            yield g * scale, int(p), int(a)
    else:
        for p, a in pairs:
            yield 0.0, int(p), int(a)


def token_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Request ``index``'s prompt: random ids from the seed, so no two requests
    share a prefix and the checker can rebuild any prompt without storing it."""
    return np.random.default_rng([seed, 2, index]) \
        .integers(1, vocab, length).astype(np.int32)


class LoadGenerator(threading.Thread):
    """Sends the plan through ``send(index, prompt_len, answer_len)`` from one
    thread and logs ``{"index", "phase", "due", "sent", "prompt_len",
    "answer_len"}``.  It starts in the WARM phase and, once ``warmed()`` says so,
    sets ``t0`` (the window's start) and goes on with the MAIN phase's plan, which
    starts a fresh pass over the tables.

    In an open loop ``due`` is the scheduled time and ``sent - due`` is how late
    the generator ran; in a closed loop a request is due when its caller's last
    one returned.  ``returned()`` is what the runner calls for each finished
    request (the closed loop's trigger)."""

    def __init__(self, traffic: dict,
                 send: Callable[[int, int, int], None],
                 warmed: Callable[[], bool], rate_rps: float = None,
                 span=None):
        super().__init__(name="bench-loadgen", daemon=True)
        self.loop = traffic["loop"]
        self.callers = int(traffic.get("callers", 0))
        self._plans = [plan(traffic, ph, rate_rps) for ph in (WARM, MAIN)]
        self._send = send
        self._warmed = warmed
        self._span = span           # context-manager factory for host spans
        self._halt = threading.Event()
        self._returned: "queue.SimpleQueue" = queue.SimpleQueue()
        self.phase = WARM
        self.t0 = None
        self.log: List[dict] = []
        self.error = None

    def returned(self) -> None:
        self._returned.put(time.monotonic())

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=30.0)

    def in_window(self, entry: dict, t1: float) -> bool:
        return entry["phase"] == MAIN and entry["due"] <= t1 + 1e-6

    def _issue(self, due: float, prompt_len: int, answer_len: int) -> None:
        index = len(self.log)
        entry = {"index": index, "phase": self.phase, "due": due, "sent": None,
                 "prompt_len": prompt_len, "answer_len": answer_len}
        self.log.append(entry)
        if self._span is not None:
            with self._span("bench.send"):
                self._send(index, prompt_len, answer_len)
        else:
            self._send(index, prompt_len, answer_len)
        entry["sent"] = time.monotonic()

    def _maybe_begin(self, t: float) -> None:
        if self.phase == WARM and self._warmed():
            self.phase, self.t0 = MAIN, t

    def run(self) -> None:
        try:
            if self.loop == "open":
                due = time.monotonic()
                while True:
                    gap, p, a = next(self._plans[self.phase])
                    due += gap
                    if self._halt.wait(max(0.0, due - time.monotonic())):
                        return
                    self._issue(due, p, a)
                    self._maybe_begin(due)
            else:
                for _ in range(self.callers):
                    _, p, a = next(self._plans[self.phase])
                    self._issue(time.monotonic(), p, a)
                while not self._halt.is_set():
                    self._maybe_begin(time.monotonic())
                    try:
                        t_back = self._returned.get(timeout=0.02)
                    except queue.Empty:
                        continue
                    _, p, a = next(self._plans[self.phase])
                    self._issue(t_back, p, a)
        except Exception as e:  # noqa: BLE001 — surfaced by the runner
            self.error = e
