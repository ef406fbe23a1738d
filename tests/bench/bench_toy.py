"""A toy copy of the benchmark for the CPU tests: a configuration at toy
widths, a small closed-loop mix and the cell ``toy.sessions``, run through
the same harness, generator and readers as the real cells."""

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TOY_CONFIG = {
    "name": "toy",
    "source": "toy widths for CPU tests",
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_hidden_layers": 1,
    "vocab_size": 256,
    "max_position_embeddings": 64,
    "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-05,
    "hidden_act": "silu",
    "qkv_bias": True,
    "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "reduced": [],
}

TOY_TRAFFIC = {
    "loop": "closed",
    "clients": 4,
    "prompt": {"median": 8, "sigma": 0.5, "min": 4, "max": 14},
    "session_max_len": 16,
    "pool": 16,
}

TOY_CELL = {
    "evaluator": "dense",
    "search": {"algo": "wu_uct", "engine": "async", "batch": 2,
               "wave_size": 2, "num_simulations": 16, "max_depth": 3,
               "max_sim_steps": 3, "beta": 1.0},
    "top_k": 4,
    "max_len": 24,
    "block_size": 4,
    "num_blocks": 64,
    "ring_capacity": 2,
    "ticks_per_segment": 8,
    "warmup_prompt_len": 8,
    "check_slots": 4,
    "limits": {"logit_rel_err": 1e-3, "select_gap": 1e-4,
               "decision_faults": 0, "unanswered": 0},
}


def add_cell(root: Path, name: str, *, config="toy", traffic="toy-sessions",
             cell=None, metrics=None):
    """Write a cell into ``root``'s BENCHMARK.json and workload files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    for m in metrics or []:
        bench["per_layer"].append(m)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "workloads" / f"{name}.json").write_text(
        json.dumps(cell or TOY_CELL))


def make_toy_root(root: Path) -> Path:
    """A copy of the benchmark (BENCHMARK.json and bench/) under ``root``
    holding the toy configuration, traffic mix and cell."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "bench" / "configs" / "toy.json").write_text(
        json.dumps(TOY_CONFIG))
    (root / "bench" / "traffic" / "toy-sessions.json").write_text(
        json.dumps(TOY_TRAFFIC))
    add_cell(root, "toy.sessions")
    return root
