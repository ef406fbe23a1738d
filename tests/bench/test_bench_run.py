"""``bench/run.py`` end to end on the CPU at toy widths.

The harness, the generator, the readers and the checks run as on the chip,
with only the look for a chip skipped: a clean run is correct, a cell and a
metric added as files alone run with no code edit, and a run whose timed
path is broken underneath comes out not correct.
"""

import copy
import io
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import checks, harness, reference, registry, system, tree_ref
from bench_toy import ROOT, TOY_CELL, add_cell


def _run(root, cell, *, seed=11, seconds=1.0, traced=False):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(cell, seed, seconds, traced,
                          t_start=time.perf_counter(), root=root,
                          require_chips=False, out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    return line, err.getvalue()


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_refuses_the_cpu():
    name = registry.benchmark()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", name,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_env(), timeout=300,
    )
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_fail(tmp_path):
    for p in registry.benchmark()["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    name = registry.benchmark()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_env(), cwd=tmp_path, timeout=300,
    )
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


@pytest.mark.parametrize("evaluator", ["dense", "paged"])
def test_toy_cell_runs_correct(toy_root, evaluator):
    cell = dict(TOY_CELL, evaluator=evaluator)
    add_cell(toy_root, f"toy.{evaluator}", cell=cell)
    line, err = _run(toy_root, f"toy.{evaluator}")
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"decisions_per_s", "ttd_p50_ms",
                                    "ttd_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] > 0
    tail = err.strip().splitlines()[-4:]
    assert [t.split()[1] for t in tail] == list(line["checks"])
    assert all(" limit " in t for t in tail)


def test_a_later_poll_with_no_live_slot_keeps_the_sample(toy_root,
                                                         monkeypatch):
    """Where the window's sample has no slot that decoded twice, later polls
    are looked at for one; once the last rows settle and release their
    slots a poll finds none live, and the sample the window left stays."""
    add_cell(toy_root, "toy.dense", cell=dict(TOY_CELL))
    real = system.slot_sample
    sizes = []

    def sample(svc, k, rng):
        s = real(svc, k, rng)
        sizes.append(s["slot"].size)
        if len(sizes) == 1:
            return dict(s, steps=np.zeros_like(s["steps"]))
        return {key: v[:0] for key, v in s.items()}

    monkeypatch.setattr(system, "slot_sample", sample)
    line, err = _run(toy_root, "toy.dense")
    assert sizes[0] > 0 and len(sizes) > 1
    assert line["correct"] is True, err[-2000:]
    assert line["checks"]["logit_rel_err"]["value"] < 1e-3


def test_cell_and_metric_added_as_files_alone(toy_root):
    (toy_root / "bench" / "metrics" / "toy_admissions.py").write_text(
        "def read(ctx):\n    return float(ctx.stats['admissions'])\n")
    (toy_root / "bench" / "traffic" / "toy-open.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 40.0,
        "prompt": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    }))
    add_cell(toy_root, "toy.open", traffic="toy-open", metrics=[{
        "name": "toy_admissions", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "device ring",
        "moves": "decisions_per_s", "workloads": ["toy.open"]}])
    line, _ = _run(toy_root, "toy.open", traced=True)
    assert line["correct"] is True
    assert line["metrics"]["toy_admissions"]["value"] > 0
    assert "ticks_per_decision" in line["metrics"]
    assert "decisions_per_s" not in line["metrics"]


BURST_LOOP = '''
"""All of a mix's requests due at once, polled until the window ends."""
import time

from bench.traffic import SPAN, Window, quantile_lengths, rng


class Burst:
    kind = "toy_burst"

    def __init__(self, params, seed, vocab):
        n = int(params["requests"])
        lengths = rng(seed, 0).permutation(quantile_lengths(params["prompt"], n))
        r = rng(seed, 1)
        self.prompts = [r.integers(1, vocab, size=int(m)).tolist()
                        for m in lengths]

    def drive(self, svc, keys, seconds):
        t0 = time.perf_counter()
        with SPAN("bench.submit"):
            pending = {svc.submit(p, key=next(keys)): t0 for p in self.prompts}
        lat = []
        while time.perf_counter() < t0 + seconds:
            with SPAN("bench.poll"):
                fresh = svc.poll()
            t = time.perf_counter()
            lat += [t - pending.pop(rid) for rid in fresh]
        return Window(t0, time.perf_counter(), lat, pending,
                      len(self.prompts), [])


def make(params, seed, vocab, seconds):
    return Burst(params, seed, vocab)
'''


def test_loop_kind_added_as_a_file_alone(toy_root):
    (toy_root / "bench" / "loops" / "toy_burst.py").write_text(BURST_LOOP)
    (toy_root / "bench" / "traffic" / "toy-burst.json").write_text(json.dumps({
        "loop": "toy_burst", "requests": 6,
        "prompt": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    }))
    add_cell(toy_root, "toy.burst", traffic="toy-burst")
    line, _ = _run(toy_root, "toy.burst", seconds=2.0)
    assert line["correct"] is True
    assert line["attempted"] == 6
    assert line["metrics"]["decisions_per_s"]["value"] > 0


def _break_answer(monkeypatch):
    """The searched action altered where it is produced."""
    from repro.core import batched_tree

    real = batched_tree.best_root_action
    monkeypatch.setattr(
        batched_tree, "best_root_action",
        lambda tree: (real(tree) + 1) % tree.children.shape[-1])


def _break_state(monkeypatch):
    """A decode step that returns its cache unchanged (the new token's K/V
    never written)."""
    import repro.models as models

    real = models.decode_step

    def step(params, cfg, token, cache):
        logits, _ = real(params, cfg, token, cache)
        return logits, dict(cache)

    monkeypatch.setattr(models, "decode_step", step)


def _break_half_batch(monkeypatch):
    """Half of the slots left out of the decode: every odd slot gets the
    logits of the even slot before it."""
    import repro.models as models

    real = models.decode_step

    def step(params, cfg, token, cache):
        logits, new = real(params, cfg, token, cache)
        return logits[(jnp.arange(logits.shape[0]) // 2) * 2], new

    monkeypatch.setattr(models, "decode_step", step)


def _break_select_first(monkeypatch):
    """The tree policy always takes the first child it may take."""
    from repro.core import batched_search

    real = batched_search.tree_select

    def select(n_c, o_c, v_c, n_p, o_p, valid, vl_c=None, **kw):
        _, score = real(n_c, o_c, v_c, n_p, o_p, valid, vl_c, **kw)
        return jnp.argmax(valid, axis=1).astype(jnp.int32), score

    monkeypatch.setattr(batched_search, "tree_select", select)


def _break_select_sign(monkeypatch):
    """The tree policy scores children by their values' opposite."""
    from repro.core import batched_search

    real = batched_search.tree_select

    def select(n_c, o_c, v_c, n_p, o_p, valid, vl_c=None, **kw):
        return real(n_c, o_c, -v_c, n_p, o_p, valid, vl_c, **kw)

    monkeypatch.setattr(batched_search, "tree_select", select)


@pytest.mark.parametrize("fault,number", [
    (_break_answer, "decision_faults"),
    (_break_state, "logit_rel_err"),
    (_break_half_batch, "logit_rel_err"),
    (_break_select_first, "select_gap"),
    (_break_select_sign, "select_gap"),
])
def test_broken_timed_path_is_not_correct(toy_root, monkeypatch, fault,
                                          number):
    fault(monkeypatch)
    line, _ = _run(toy_root, "toy.sessions")
    assert line["correct"] is False
    c = line["checks"][number]
    assert c["value"] > c["limit"]


def test_control_fails_where_the_program_passes(toy_root):
    """At toy widths in bf16, the harness's own comparison passes the
    program and fails the control put in its place: the float8 reference's
    logits and the bfloat16 tree policy at the same slots and trees (the
    cell's limits lie between the two readings on the chip; see PERF.md)."""
    cfg_file = toy_root / "bench" / "configs" / "toy.json"
    conf = json.loads(cfg_file.read_text())
    conf.update(torch_dtype="bfloat16", hidden_size=128, head_dim=32)
    cfg_file.write_text(json.dumps(conf))
    cell = copy.deepcopy(TOY_CELL)
    # Toy readings: the program about 0.005, the control 0.045-0.058.
    cell["limits"]["logit_rel_err"] = 0.02
    add_cell(toy_root, "toy.bf16", cell=cell)
    c = registry.cell("toy.bf16", toy_root)
    served = harness.serve(c, 5, 1.0, False, t_start=time.perf_counter(),
                           root=toy_root)
    r = harness.read(served)
    assert checks.passed(harness.compare(served, c["limits"], r))
    s, tree, beta = served.sample, served.tree, c["search"]["beta"]
    control = harness.Readings(
        want=r.want,
        logit_errs=reference.rel_l2(served.arch.last_logits(
            served.weights, c["config"], s["tokens"], s["len"], quant="fp8"),
            r.want),
        select_gaps=tree_ref.select_gaps(
            tree, tree_ref.control_choice(tree, beta), beta),
    )
    verdict = harness.compare(served, c["limits"], control)
    assert not checks.passed(verdict)
    assert verdict["logit_rel_err"]["value"] > 3 * r.logit_errs.max()


def test_tree_reference_agrees_with_the_programs_selection():
    """On random statistics, in-flight counts, pending and untried children
    included, the plain WU-UCT rule finds no gap in the program's
    ``tree_select`` (kernel and jnp paths) and finds one in a flipped
    policy and in the first child taken."""
    from repro.core.batched_search import batched_select
    from repro.core.batched_tree import BatchedTree
    from repro.core.policies import PolicyConfig

    rng = np.random.default_rng(3)
    b, m, a = 4, 9, 5
    children = np.full((b, m, a), -1, np.int32)
    nxt = np.ones(b, np.int32)
    for t in range(b):
        for node in range(3):
            for act in rng.choice(a, size=rng.integers(2, a + 1),
                                  replace=False):
                if nxt[t] < m:
                    children[t, node, act] = nxt[t]
                    nxt[t] += 1
    f32 = np.float32
    stats = dict(N=rng.integers(0, 9, (b, m)).astype(f32),
                 O=rng.integers(0, 3, (b, m)).astype(f32),
                 V=rng.normal(size=(b, m)).astype(f32),
                 pending=rng.random((b, m)) < 0.15)
    tree = BatchedTree(
        parent=jnp.zeros((b, m), jnp.int32), action=jnp.zeros((b, m), jnp.int32),
        children=jnp.asarray(children), N=jnp.asarray(stats["N"]),
        O=jnp.asarray(stats["O"]), V=jnp.asarray(stats["V"]),
        VL=jnp.zeros((b, m)), R=jnp.zeros((b, m)),
        terminal=jnp.zeros((b, m), bool), pending=jnp.asarray(stats["pending"]),
        depth=jnp.zeros((b, m), jnp.int32), size=jnp.asarray(nxt),
        overflowed=jnp.zeros((b,), bool), states=jnp.zeros((b, m)))
    host = dict(stats, children=children)
    for kernel in (True, False):
        acts = np.stack([np.asarray(batched_select(
            tree, jnp.full((b,), node, jnp.int32), PolicyConfig(), kernel)[0])
            for node in range(m)], axis=1)
        gaps = tree_ref.select_gaps(host, acts, 1.0)
        assert gaps.size > 2 * b and gaps.max() < 1e-6
    _, may = tree_ref.scores(host, 1.0)
    flipped, _ = tree_ref.scores(dict(host, V=-stats["V"]), 1.0)
    assert tree_ref.select_gaps(host, np.argmax(flipped, -1), 1.0).max() > 0.1
    assert tree_ref.select_gaps(host, np.argmax(may, -1), 1.0).max() > 0.1


def test_reference_matches_the_programs_forward_in_f32():
    """The reference, written apart from the program, agrees with the
    program's own full forward at toy widths in float32."""
    from repro.models import forward

    from bench_toy import TOY_CONFIG

    conf = dict(TOY_CONFIG, num_hidden_layers=2)
    arch = registry.arch(conf)
    cfg = arch.model_config(conf)
    weights = system.make_weights(cfg, 3)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, 256, size=(3, 24)).astype(np.int32)
    lens = np.array([5, 17, 24], np.int32)
    want = arch.last_logits(weights, conf, tokens, lens,
                            tokens_per_block=48)
    with jax.default_matmul_precision("highest"):
        full, _ = forward(weights, cfg, {"tokens": jnp.asarray(tokens)})
    got = np.asarray(full)[np.arange(3), lens - 1]
    assert reference.rel_l2(got, want).max() < 1e-5
