"""``chip_smoke.py`` on the CPU: its phases at a tiny size, and its refusal
to run without a TPU.

The script itself runs only on a chip; these tests call the same phase
functions on a model cut to toy widths (Pallas kernels in interpret mode),
so a wrong path, argument or check fails here before it costs chip time.
"""

import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import SearchSpec

REPO = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.fixture(scope="module")
def tiny(smoke):
    """Qwen2.5's family and dtype (bf16, QKV bias, GQA) at toy widths."""
    cfg = dataclasses.replace(
        smoke.smoke_config(layers=1), d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
    )
    return cfg, smoke.init_model(cfg, 0)


def test_main_refuses_to_run_without_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Copied away from the repository, the script cannot run at all."""
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text()
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_kernel_phase_at_tiny_size(smoke, tiny):
    cfg, _ = tiny
    errs = smoke.check_kernels(cfg, 0, slots=8, max_len=32, block_size=8,
                               top_k=4, batch=2)
    assert set(errs) == {
        "decode_attention", "paged_decode_attention", "tree_decode_attention",
        "paged_tree_decode_attention", "flash_attention", "tree_select",
    }


def test_kernel_widths_phase_at_tiny_size(smoke, tiny, monkeypatch):
    """Each distinct width once, the smoke model's own skipped; two families
    stand in for the rest (interpret mode is slow at 32+ heads)."""
    cfg, _ = tiny
    monkeypatch.setattr(smoke, "list_archs", lambda: [
        "qwen2.5-32b", "whisper-small", "zamba2-7b", "mamba2-2.7b",
        "whisper-small",
    ])
    errs = smoke.check_kernel_widths(
        smoke.smoke_config(), 0, slots=2, max_len=16, block_size=8, top_k=2,
        batch=2,
    )
    assert set(errs) == {"12x12x64", "32x32x112"}


def test_gradient_phase_at_tiny_size(smoke, tiny):
    cfg, _ = tiny
    err, kernels = smoke.check_gradients(
        dataclasses.replace(cfg, attn_impl="pallas"), 0, batch=2, seq=32
    )
    assert 0.0 <= err <= smoke.GRAD_RTOL
    assert kernels == set()   # interpret mode: no TPU custom calls


def test_logits_phase_at_tiny_size(smoke, tiny):
    cfg, params = tiny
    errs = smoke.check_cached_logits(cfg, params, 0, prompt_lens=(3, 7, 20),
                                     steps=3, max_len=32)
    assert 0.0 < errs["cached"] <= smoke.LOGITS_RTOL
    assert 0.0 < errs["plain_bf16"]


@pytest.mark.parametrize("path", ["dense", "paged", "paged_frontier"])
def test_serve_phase_at_tiny_size(smoke, tiny, path):
    cfg, params = tiny
    spec = SearchSpec(
        algo="wu_uct", engine="async", batch=2, wave_size=2,
        num_simulations=6, max_depth=3, max_sim_steps=3,
    )
    prompts = smoke.make_prompts(5, 2, 8, cfg.vocab_size, 0)
    svc = smoke.make_service(cfg, params, path, spec=spec, top_k=4,
                             max_len=32, block_size=8)
    out = smoke.serve_requests(svc, prompts, top_k=4)
    assert out["requests"] == len(prompts)
    assert svc.stats.completed == svc.stats.submitted == len(prompts) + 1
