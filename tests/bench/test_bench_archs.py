"""Architecture modules (``bench/archs/``): the dense module gives the
numbers the harness computed before it took them from a module, a
configuration that names another architecture is served, checked and
counted through that module with no edit to a file the benchmark has, and
a file the dense module does not model is refused."""

import dataclasses
import hashlib
import io
import json
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import harness, registry
from bench.system import ModelConfig
from bench_toy import ROOT, TOY_CONFIG, add_cell

dense = registry.arch({})

# The program's configuration of each committed file, as the harness built
# it before architectures were modules.
MODEL_CONFIGS = {
    "qwen2.5-32b-l4": ModelConfig(
        name="qwen2.5-32b-l4", family="dense", num_layers=4, d_model=5120,
        num_heads=40, num_kv_heads=8, head_dim=128, d_ff=27648,
        vocab_size=152064, qkv_bias=True, rope_theta=1e6, rms_eps=1e-5,
        tie_embeddings=False, dtype=jnp.bfloat16),
    "deepseek-67b-l4": ModelConfig(
        name="deepseek-67b-l4", family="dense", num_layers=4, d_model=8192,
        num_heads=64, num_kv_heads=8, head_dim=128, d_ff=22016,
        vocab_size=102400, qkv_bias=False, rope_theta=1e4, rms_eps=1e-6,
        tie_embeddings=False, dtype=jnp.bfloat16),
}

# Matmul parameters of one layer and of the LM head, counted by hand: q
# and o d x d, k and v d x 1024 each, three MLP projections d x d_ff; the
# head d x vocab.
HAND_COUNTS = {
    # 2 x 26,214,400 + 2 x 5,242,880 + 3 x 141,557,760; 5120 x 152064
    "qwen2.5-32b-l4": (487_587_840, 778_567_680),
    # 2 x 67,108,864 + 2 x 8,388,608 + 3 x 180,355,072; 8192 x 102400
    "deepseek-67b-l4": (692_060_160, 838_860_800),
}

ARCH_FILES = sorted(p.stem for p in (ROOT / "bench" / "archs").glob("*.py")
                    if p.stem != "__init__")


@pytest.mark.parametrize("name", sorted(MODEL_CONFIGS))
def test_dense_builds_the_same_model_config(name):
    c = registry.config(name)
    assert "arch" not in c
    assert registry.arch(c).model_config(c) == MODEL_CONFIGS[name]


@pytest.mark.parametrize("name", sorted(HAND_COUNTS))
def test_dense_window_flops_match_a_hand_count(name):
    c = registry.config(name)
    layer, head = HAND_COUNTS[name]
    arch = registry.arch(c)
    assert (arch.layer_matmul_params(c), arch.head_params(c)) == (layer, head)
    stats = {"busy_tree_ticks": 37, "admissions": 5, "ticks": 40}
    got = arch.window_flops(c, stats, wave=8, max_len=384)
    assert got == 2.0 * (37 * 8 * (4 * layer + head)
                         + 5 * (384 * 4 * layer + head))


@pytest.mark.parametrize("name", sorted(MODEL_CONFIGS))
def test_dense_attention_kv_bytes_match_the_readers_formula(name):
    c = registry.config(name)
    stats = {"attended_positions": 123_457}
    want = (123_457 * c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * {"bfloat16": 2, "float32": 4}[c["torch_dtype"]])
    assert registry.arch(c).attention_kv_bytes(c, stats) == want
    # 4 layers x K and V x 8 heads x 128 x 2 bytes = 16 KiB a position.
    assert want == 123_457 * 16384
    assert registry.arch(c).attention_kv_bytes(c, {"ticks": 3}) == 0


@pytest.mark.parametrize("name", ARCH_FILES)
def test_every_arch_module_provides_the_interface(name):
    mod = registry.module("archs", name)
    for fn in ("model_config", "last_logits", "window_flops",
               "attention_kv_bytes"):
        assert callable(getattr(mod, fn)), fn


@pytest.mark.parametrize("name", ARCH_FILES)
def test_arch_module_imports_nothing_of_the_program(name):
    """Loading an architecture module, its reference with it, leaves the
    program (``repro``) unimported; only ``model_config`` reaches it."""
    code = (f"import sys; from bench import registry; "
            f"registry.arch({{'arch': {name!r}}}); "
            f"print(sorted(m for m in sys.modules if m.split('.')[0] == "
            f"'repro'))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                         capture_output=True, text=True).stdout
    assert out.strip() == "[]"


@pytest.mark.parametrize("name", ARCH_FILES)
def test_arch_module_is_loaded_once_a_root(name, tmp_path):
    """The harness's runs in one process (``calibrate.py``'s seeds, the
    toy cells) share one module, so its jitted reference compiles once."""
    c = {"arch": name}
    assert registry.arch(c) is registry.arch(c, ROOT / "bench" / "..")
    (tmp_path / "bench" / "archs").mkdir(parents=True)
    (tmp_path / "bench" / "archs" / f"{name}.py").write_bytes(
        (ROOT / "bench" / "archs" / f"{name}.py").read_bytes())
    assert registry.arch(c, tmp_path) is not registry.arch(c)


@pytest.mark.parametrize("key", dense.UNMODELLED)
def test_dense_refuses_a_key_it_does_not_model(key):
    with pytest.raises(ValueError, match=key):
        dense.model_config(dict(TOY_CONFIG, **{key: 8}))


def test_unknown_arch_is_a_key_error():
    with pytest.raises(KeyError, match="no_such_arch.py"):
        registry.arch(dict(TOY_CONFIG, arch="no_such_arch"))


PROBE = '''
"""The dense architecture with its FLOP count doubled; ``calls`` names each
function the harness called."""
from pathlib import Path

from bench import registry

dense = registry.module("archs", "dense", Path(__file__).resolve().parents[2])
calls = []


def _logged(fn):
    def call(*args, **kw):
        calls.append(fn.__name__)
        return fn(*args, **kw)
    return call


model_config = _logged(dense.model_config)
last_logits = _logged(dense.last_logits)
attention_kv_bytes = _logged(dense.attention_kv_bytes)


@_logged
def window_flops(c, stats, *, wave, max_len):
    return 2 * dense.window_flops(c, stats, wave=wave, max_len=max_len)
'''


def _digests(root: Path, files) -> dict:
    return {f: hashlib.sha256((root / f).read_bytes()).hexdigest()
            for f in files}


def test_architecture_added_as_files_alone(toy_root, monkeypatch):
    """A configuration naming ``"arch": "probe"`` and the module
    ``bench/archs/probe.py`` beside it are served, checked and counted
    through that module: the run is correct and ``step_mfu`` reads twice
    what the dense count gives for the same window.  No file the benchmark
    had is changed; BENCHMARK.json only gains the cell."""
    had = sorted(str(p.relative_to(ROOT)) for p in (ROOT / "bench").rglob("*")
                 if p.is_file() and "__pycache__" not in p.parts)
    before = _digests(ROOT, had)
    bench_before = json.loads((ROOT / "BENCHMARK.json").read_text())

    (toy_root / "bench" / "archs" / "probe.py").write_text(PROBE)
    (toy_root / "bench" / "configs" / "toy-probe.json").write_text(
        json.dumps(dict(TOY_CONFIG, name="toy-probe", arch="probe")))
    add_cell(toy_root, "toy-probe.sessions", config="toy-probe")

    # The CPU has no row in bench/peaks.json; the v5e's stands in, as the
    # ratio read here does not depend on it.
    v5e = json.loads((ROOT / "bench" / "peaks.json").read_text())
    seen = []

    @dataclasses.dataclass
    class Recorded(harness.Context):
        def __post_init__(self):
            self.peak = self.peak or v5e["devices"]["TPU v5 lite"]
            seen.append(self)

    monkeypatch.setattr(harness, "Context", Recorded)
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell("toy-probe.sessions", 11, 1.0, True,
                          t_start=time.perf_counter(), root=toy_root,
                          require_chips=False, out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0

    (ctx,) = seen
    assert ctx.arch.__name__ == "bench_archs_probe"
    assert sorted(set(ctx.arch.calls)) == [
        "attention_kv_bytes", "last_logits", "model_config", "window_flops"]
    c = ctx.cell["config"]
    counted = dense.window_flops(c, ctx.stats, wave=ctx.cell["search"][
        "wave_size"], max_len=ctx.cell["max_len"])
    assert counted > 0 and ctx.flops == 2 * counted
    as_dense = registry.metric_reader("step_mfu")(
        dataclasses.replace(ctx, flops=counted))
    assert line["metrics"]["step_mfu"]["value"] == pytest.approx(
        2 * as_dense, rel=1e-12)

    assert _digests(toy_root, had) == before
    bench = json.loads((toy_root / "BENCHMARK.json").read_text())
    n = len(bench_before["workloads"])
    assert bench["workloads"][:n] == bench_before["workloads"]
    assert bench["configs"] == bench_before["configs"]
    assert bench["end_to_end"] == bench_before["end_to_end"]
    for m, m0 in zip(bench["per_layer"], bench_before["per_layer"]):
        assert m["workloads"][:len(m0["workloads"])] == m0["workloads"]
        assert {k: v for k, v in m.items() if k != "workloads"} == \
            {k: v for k, v in m0.items() if k != "workloads"}
