"""Closed loop: ``clients`` search-based decoding sessions.

Each client submits a prompt, waits for the decision, appends a token chosen
by that decision and asks again; once the prompt would pass
``session_max_len`` it starts a new session.  Parameters: ``clients``,
``prompt`` (lognormal lengths), ``session_max_len`` and ``pool`` (how many
prompt lengths are drawn; sessions take them in turn).
"""

from __future__ import annotations

import time

from bench.traffic import SPAN, Window, quantile_lengths, rng


class ClosedLoop:
    """``clients`` sessions, each waiting for its decision before it asks
    again."""

    kind = "closed"

    def __init__(self, params: dict, seed: int, vocab: int):
        self.params = params
        self.clients = int(params["clients"])
        self.vocab = int(vocab)
        self.limit = int(params["session_max_len"])
        pool = quantile_lengths(params["prompt"], int(params["pool"]))
        self.lengths = rng(seed, 0).permutation(pool)
        self._rngs = [rng(seed, 1, c) for c in range(self.clients)]
        self._sessions = [0] * self.clients
        self.prompts = [self._new_session(c) for c in range(self.clients)]

    def _new_session(self, c: int) -> list:
        k = self._sessions[c]
        self._sessions[c] += 1
        n = int(self.lengths[(c + k * self.clients) % len(self.lengths)])
        return self._rngs[c].integers(1, self.vocab, size=n).tolist()

    def decided(self, c: int, action: int) -> list:
        """Client ``c``'s next prompt after the decision ``action``."""
        base = int(self._rngs[c].integers(0, self.vocab - 1))
        token = 1 + (base + int(action)) % (self.vocab - 1)
        prompt = self.prompts[c] + [token]
        if len(prompt) > self.limit:
            prompt = self._new_session(c)
        self.prompts[c] = prompt
        return prompt

    def drive(self, svc, keys, seconds: float) -> Window:
        """Every client's first prompt at the start, then each client's next
        as soon as its decision is in, until ``seconds`` have passed."""
        clock = time.perf_counter
        pending = {}
        lat = []
        attempted = 0

        def submit(c, prompt, t):
            nonlocal attempted
            with SPAN("bench.submit"):
                pending[svc.submit(prompt, key=next(keys))] = (c, t)
            attempted += 1

        t0 = clock()
        for c in range(self.clients):
            submit(c, self.prompts[c], t0)
        end = t0 + seconds
        while clock() < end:
            with SPAN("bench.poll"):
                fresh = svc.poll()
            t = clock()
            for rid in sorted(fresh):
                c, ts = pending.pop(rid)
                lat.append(t - ts)
                nxt = self.decided(c, int(fresh[rid].action))
                if t < end:
                    submit(c, nxt, t)
        t1 = clock()
        return Window(t0, t1, lat, {r: ts for r, (_, ts) in pending.items()},
                      attempted, [])


def make(params: dict, seed: int, vocab: int, seconds: float) -> ClosedLoop:
    return ClosedLoop(params, seed, vocab)
