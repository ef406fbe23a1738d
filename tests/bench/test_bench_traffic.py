"""The traffic generator: deterministic per seed, and the same work for
every seed in another order."""

import numpy as np
import pytest

from bench import registry, traffic

BIG = 2 ** 31 + 12345                     # seeds pass 32 signed bits
MIXES = sorted(p.stem for p in (registry.ROOT / "bench" / "traffic").glob(
    "*.json"))


def _make(name, seed, seconds=30.0):
    return traffic.make(registry.traffic(name), seed, 1000, seconds)


def _trace(gen):
    """What the generator sends: closed loop, each client's first prompt
    and the prompts that follow ten decisions; open loop, the schedule."""
    if gen.kind == "open":
        return [list(gen.due)] + gen.prompts
    out = [list(p) for p in gen.prompts]
    for step in range(10):
        for c in range(gen.clients):
            out.append(gen.decided(c, step % 8))
    return out


@pytest.mark.parametrize("name", MIXES)
def test_generators_are_deterministic_per_seed(name):
    assert _trace(_make(name, BIG)) == _trace(_make(name, BIG))
    assert _trace(_make(name, BIG)) != _trace(_make(name, BIG + 1))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_draws_the_same_sizes(name):
    a, b = _make(name, 1), _make(name, BIG)
    if a.kind == "open":
        assert sorted(len(p) for p in a.prompts) == sorted(
            len(p) for p in b.prompts)
        assert sorted(a.gaps) == sorted(b.gaps)
        assert list(a.gaps) != list(b.gaps)
    else:
        assert sorted(a.lengths) == sorted(b.lengths)
        assert list(a.lengths) != list(b.lengths)


@pytest.mark.parametrize("name", MIXES)
def test_prompts_stay_inside_the_cells_caches(name):
    p = registry.traffic(name)
    gen = _make(name, 7)
    lo, hi = p["prompt"]["min"], p["prompt"]["max"]
    if gen.kind == "open":
        assert all(lo <= len(x) <= hi for x in gen.prompts)
        return
    for step in range(200):
        for c in range(gen.clients):
            prompt = gen.decided(c, step % 8)
            assert lo <= len(prompt) <= p["session_max_len"]
            assert min(prompt) >= 1             # 0 ends a sequence


def test_quantile_lengths_follow_the_distribution():
    p = {"median": 160, "sigma": 0.5, "min": 64, "max": 448}
    xs = traffic.quantile_lengths(p, 1001)
    assert xs[500] == 160 and xs.min() >= 64 and xs.max() <= 448
    assert np.all(np.diff(xs) >= 0)


def test_cells_leave_room_for_the_search_in_the_cache():
    for w in registry.benchmark()["workloads"]:
        c = registry.cell(w["name"])
        t, s = c["traffic"], c["search"]
        longest = (t["session_max_len"] if t["loop"] == "closed"
                   else t["prompt"]["max"])
        assert longest + s["max_depth"] + s["max_sim_steps"] < c["max_len"]
