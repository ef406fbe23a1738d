"""Find the benchmark's parts by the names ``BENCHMARK.json`` gives them.

Every lookup takes the checkout root, so the tests can point it at a copy
that holds extra cells, metrics, loops or architectures added as files
alone.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from typing import Callable

ROOT = Path(__file__).resolve().parents[1]


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _json(root: Path, kind: str, name: str) -> dict:
    path = Path(root) / "bench" / kind / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no {kind} file for {name!r} at {path}")
    return json.loads(path.read_text())


def config(name: str, root: Path = ROOT) -> dict:
    return _json(root, "configs", name)


def traffic(name: str, root: Path = ROOT) -> dict:
    return _json(root, "traffic", name)


def cell(name: str, root: Path = ROOT) -> dict:
    """The cell's ``BENCHMARK.json`` entry joined with its workload file,
    its configuration and its traffic mix, and the metrics it reports."""
    bench = benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                       f"{sorted(entries)}")
    entry = entries[name]
    spec = _json(root, "workloads", name)

    def reported(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]

    return {
        **spec,
        "name": name,
        "chips": entry["chips"],
        "config": config(entry["config"], root),
        "traffic": traffic(entry["traffic"], root),
        "end_to_end": reported(bench["end_to_end"]),
        "per_layer": reported(bench["per_layer"]),
    }


def module(kind: str, name: str, root: Path = ROOT):
    """The module ``bench/<kind>/<name>.py``: a metric reader, a loop or an
    architecture."""
    path = Path(root) / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} module for {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch(config: dict, root: Path = ROOT):
    """The architecture module ``bench/archs/<arch>.py`` that a
    configuration file names under ``"arch"`` (``dense`` where it names
    none).  Loaded once a process for each root, so its jitted reference
    compiles once however many runs the process makes."""
    return _arch(Path(root).resolve(), config.get("arch", "dense"))


@functools.lru_cache(maxsize=None)
def _arch(root: Path, name: str):
    return module("archs", name, root)


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return module("metrics", name, root).read
