"""Open loop: independent requests with Poisson arrivals at ``rate_per_s``.

Parameters: ``rate_per_s`` and ``prompt`` (lognormal lengths).  A window of
``seconds`` schedules ``ceil(rate * seconds)`` requests; time to decision
runs from each request's due time.
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench.traffic import SPAN, Window, quantile_lengths, rng


class OpenLoop:
    """Independent requests due at Poisson arrival times over the window."""

    kind = "open"

    def __init__(self, params: dict, seed: int, vocab: int, seconds: float):
        rate = float(params["rate_per_s"])
        n = max(1, math.ceil(rate * seconds))
        q = (np.arange(n) + 0.5) / n
        self.gaps = rng(seed, 0).permutation(-np.log1p(-q) / rate)
        self.due = np.cumsum(self.gaps) - self.gaps[0]
        lengths = rng(seed, 1).permutation(
            quantile_lengths(params["prompt"], n)
        )
        r = rng(seed, 2)
        self.prompts = [
            r.integers(1, vocab, size=int(m)).tolist() for m in lengths
        ]

    def drive(self, svc, keys, seconds: float) -> Window:
        """Each request submitted at its due time (or as soon after it as
        the host is free), polls in between."""
        clock = time.perf_counter
        pending = {}
        lat, lateness = [], []
        i, n = 0, len(self.due)
        t0 = clock()
        end = t0 + seconds
        while True:
            now = clock()
            while i < n and t0 + self.due[i] <= min(now, end):
                due = t0 + float(self.due[i])
                with SPAN("bench.submit"):
                    pending[svc.submit(self.prompts[i], key=next(keys))] = due
                lateness.append(now - due)
                i += 1
            if now >= end:
                break
            if not pending:
                nxt = t0 + float(self.due[i]) if i < n else end
                with SPAN("bench.wait"):
                    time.sleep(max(0.0, min(nxt, end) - clock()))
                continue
            with SPAN("bench.poll"):
                fresh = svc.poll()
            t = clock()
            for rid in fresh:
                lat.append(t - pending.pop(rid))
        return Window(t0, clock(), lat, pending, i, lateness)


def make(params: dict, seed: int, vocab: int, seconds: float) -> OpenLoop:
    return OpenLoop(params, seed, vocab, seconds)
