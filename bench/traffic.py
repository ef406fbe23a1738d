"""The traffic generator: every mix is a file of parameters it reads.

A mix (``bench/traffic/<name>.json``) names its arrival process by
``"loop"``; each process is a module of its own, ``bench/loops/<loop>.py``,
found by that name as the metric readers are.  A module gives
``make(params, seed, vocab, seconds)``, which returns the generator, and
the generator's ``drive(svc, keys, seconds)`` submits to the service and
polls it for the window, returning a :class:`Window`.

So that a seed changes the order of the work and not its amount, every
seed draws the same set of prompt lengths (and, open loop, of gaps between
arrivals): the quantiles of the stated distribution, in an order the seed
shuffles.  Token ids are uniform over ``[1, vocab)`` (0 is the end-of-text
token the search stops on) and differ by seed.
"""

from __future__ import annotations

import dataclasses
import math
from pathlib import Path
from statistics import NormalDist

import jax
import numpy as np

from . import registry

#: Host spans the trace reduction names idle gaps after.
SPAN = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    latencies: list          # seconds, every request answered in the window
    late: dict               # req_id -> start time (due or submit), open
    attempted: int
    lateness: list           # open loop: submit time minus due time


def quantile_lengths(p: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a lognormal length distribution
    (``median``, ``sigma`` of the log), rounded and clipped to
    ``[min, max]``."""
    inv = NormalDist().inv_cdf
    mu = math.log(p["median"])
    raw = [math.exp(mu + p["sigma"] * inv((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(raw), p["min"], p["max"]).astype(np.int64)


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def make(params: dict, seed: int, vocab: int, seconds: float,
         root: Path = registry.ROOT):
    """The generator of the mix ``params``, by its ``"loop"``."""
    loop = registry.module("loops", params["loop"], root)
    return loop.make(params, seed, vocab, seconds)
