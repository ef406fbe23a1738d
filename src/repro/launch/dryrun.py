import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "")
)

# ---------------------------------------------------------------------------
# Multi-pod dry-run: lower + compile every (architecture × input-shape) cell
# for the production meshes and extract the roofline terms from the compiled
# artifact.  This file proves the distribution config is coherent without
# real hardware — any sharding mismatch, compile-OOM or unsupported
# collective is a bug in the system, not in the harness.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
#       --shape train_4k --mesh single_pod
#   PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun
# ---------------------------------------------------------------------------

import argparse
import json
import re
import time
import traceback
from typing import Optional

import jax

from repro.compile_cache import use_compile_cache
from repro.launch.cells import SHAPES, all_cells, build_cell, skip_reason
from repro.launch.mesh import make_production_mesh, make_test_mesh

# The failure modes a dry-run cell can legitimately hit: sharding/shape
# mismatches (ValueError/TypeError), compile failures and XLA OOM
# (RuntimeError — XlaRuntimeError subclasses it), missing cell config keys
# (KeyError/AttributeError), unsupported collectives (NotImplementedError)
# and artifact IO (OSError).  Anything else — e.g. a KeyboardInterrupt or a
# typo-level NameError — should crash the sweep, not be recorded as a cell
# failure.
_CELL_ERRORS = (
    RuntimeError, ValueError, TypeError, KeyError, AttributeError,
    IndexError, NotImplementedError, OSError, ArithmeticError,
)

# TPU v5e hardware constants (assignment-specified).
PEAK_FLOPS = 197e12       # bf16 FLOP/s per chip
HBM_BW = 819e9            # bytes/s per chip
LINK_BW = 50e9            # bytes/s per chip (effective ICI collective bw)

_COLL_RE = re.compile(
    r"^\s*(?:%|\S+ = )?"
    r"(?P<shape>\(?[a-z0-9]+\[[0-9,]*\][^ ]*\)?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO shape string like 'bf16[8,128]{1,0}' or a tuple."""
    total = 0
    for m in re.finditer(r"([a-z][a-z0-9]*)\[([0-9,]*)\]", shape_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-chip wire bytes per collective type, parsed from post-SPMD HLO.

    The compiled module is the per-device SPMD program, so result shapes are
    shard shapes.  Wire-cost model (ring algorithms, group size n):
      all-gather:        out_bytes * (n-1)/n     ≈ out_bytes
      all-reduce:        2 * bytes * (n-1)/n     ≈ 2 * bytes
      reduce-scatter:    in_bytes  * (n-1)/n     ≈ out_bytes * (n-1)
      all-to-all:        bytes * (n-1)/n
      collective-permute: bytes
    We use the ≈ forms (upper bounds) with n from replica_groups when
    parseable.
    """
    out = {k: 0.0 for k in (
        "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute")}
    counts = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        if "fused_computation" in line:
            continue
        m = re.search(
            r"= (?P<shape>\(?[^=]*?\)?) (?P<op>all-gather|all-reduce|"
            r"reduce-scatter|all-to-all|collective-permute)(?:-start)?\(",
            line,
        )
        if not m:
            continue
        op = m.group("op")
        nbytes = _shape_bytes(m.group("shape"))
        gm = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
        n = int(gm.group(2)) if gm else 2
        if op == "all-gather":
            out[op] += nbytes * (n - 1) / max(n, 1)
        elif op == "all-reduce":
            out[op] += 2 * nbytes * (n - 1) / max(n, 1)
        elif op == "reduce-scatter":
            out[op] += nbytes * (n - 1)
        elif op == "all-to-all":
            out[op] += nbytes * (n - 1) / max(n, 1)
        else:
            out[op] += nbytes
        counts[op] += 1
    out["total"] = sum(out.values())
    out["counts"] = counts
    return out


def model_flops(cell, mesh_devices: int) -> float:
    """6·N·D bookkeeping (N = active params for MoE)."""
    cfg = cell.model_cfg
    n = cfg.active_param_count()
    if cell.kind == "train":
        return 6.0 * n * cell.tokens_per_step
    return 2.0 * n * cell.tokens_per_step


def _compile_cell(cell, mesh):
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
        )
        lowered = jitted.lower(*cell.arg_specs)
        compiled = lowered.compile()
    return lowered, compiled


def _cost_point(arch, shape, mesh, overrides, strategy="tp", kv_mode=None) -> dict:
    """Per-device (flops, bytes, collectives) for a small UNROLLED config.

    XLA's cost analysis counts while-loop bodies once, so the scanned full
    model under-reports per-layer work.  We therefore compile 2-3 small
    *unrolled* configs with identical per-device activation shapes and solve
    the affine model cost(L) = base + L·layer (+ sites·site for hybrid).
    """
    cell = build_cell(
        arch, shape, mesh, cfg_overrides=overrides,
        strategy=strategy, kv_mode=kv_mode,
    )
    _, compiled = _compile_cell(cell, mesh)
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll["total"],
        "coll_detail": {k: coll[k] for k in (
            "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute")},
    }


def _lin_combine(points: dict[int, dict], weights: dict[int, float]) -> dict:
    keys = ("flops", "bytes", "coll")
    out = {k: 0.0 for k in keys}
    detail = {}
    for L, w in weights.items():
        for k in keys:
            out[k] += w * points[L][k]
        for k, v in points[L]["coll_detail"].items():
            detail[k] = detail.get(k, 0.0) + w * v
    out["coll_detail"] = {k: max(v, 0.0) for k, v in detail.items()}
    return {k: (max(v, 0.0) if not isinstance(v, dict) else v) for k, v in out.items()}


def measure_roofline_terms(
    arch, shape, mesh, overrides=None, strategy="tp", kv_mode=None
) -> dict:
    """Extrapolated per-device totals for the real layer count."""
    from repro.configs import get_config

    cfg = get_config(arch)
    # Keep remat ON so the compute term includes real recompute FLOPs.
    base_over = dict(overrides or {})
    base_over["scan_layers"] = False
    if cfg.family == "hybrid":
        k = cfg.attn_every
        pts = {}
        for L in (k, k + 1, 2 * k):
            pts[L] = _cost_point(
                arch, shape, mesh, dict(base_over, num_layers=L),
                strategy, kv_mode,
            )
        # f(L) = base + L*ssm + sites(L)*site; sites(k)=1, sites(k+1)=2, sites(2k)=2
        # ssm  = (f(2k) - f(k+1)) / (k - 1)
        # site = f(k+1) - f(k) - ssm
        # base = f(k) - k*ssm - site
        L_real, sites_real = cfg.num_layers, (cfg.num_layers + k - 1) // k
        den = k - 1
        w_ssm = {2 * k: 1.0 / den, k + 1: -1.0 / den}
        # site = f(k+1) - f(k) - ssm
        w_site = {k + 1: 1.0 + 1.0 / den, k: -1.0, 2 * k: -1.0 / den}
        # base = f(k) - k*ssm - site
        w_base = {
            k: 2.0,
            k + 1: -(1.0 + 1.0 / den) + (k * 1.0 / den),
            2 * k: 1.0 / den - k * 1.0 / den,
        }
        weights = {}
        for L in pts:
            weights[L] = (
                w_base.get(L, 0.0)
                + L_real * w_ssm.get(L, 0.0)
                + sites_real * w_site.get(L, 0.0)
            )
        return _lin_combine(pts, weights)

    pts = {}
    for L in (1, 2):
        over = dict(base_over, num_layers=L)
        if cfg.family == "encdec":
            over["num_encoder_layers"] = L
        pts[L] = _cost_point(arch, shape, mesh, over, strategy, kv_mode)
    L_real = cfg.num_layers  # == num_encoder_layers for whisper
    # slope = f(2) - f(1); base = f(1) - slope; total = base + L*slope
    weights = {1: 1.0 - (L_real - 1.0), 2: (L_real - 1.0)}
    return _lin_combine(pts, weights)


def run_cell(
    arch: str, shape: str, mesh, mesh_name: str, verbose=True,
    overrides: Optional[dict] = None, measure: bool = True,
    strategy: str = "tp", kv_mode: Optional[str] = None,
) -> dict:
    t0 = time.time()
    cell = build_cell(
        arch, shape, mesh, cfg_overrides=overrides,
        strategy=strategy, kv_mode=kv_mode,
    )
    lowered, compiled = _compile_cell(cell, mesh)
    t_full = time.time() - t0

    mem = compiled.memory_analysis()
    coll_full = collective_bytes(compiled.as_text())
    n_dev = mesh.devices.size

    terms = (
        measure_roofline_terms(arch, shape, mesh, overrides, strategy, kv_mode)
        if measure
        else None
    )
    t_measure = time.time() - t0 - t_full

    result = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "devices": n_dev,
        "kind": cell.kind,
        "overrides": overrides or {},
        "strategy": strategy,
        "kv_mode": kv_mode,
        "compile_s": round(t_full, 1),
        "measure_s": round(t_measure, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
            ),
        },
        "full_module_collectives": coll_full["counts"],
    }
    if terms is not None:
        compute_s = terms["flops"] / PEAK_FLOPS
        memory_s = terms["bytes"] / HBM_BW
        collective_s = terms["coll"] / LINK_BW
        dominant = max(
            ("compute", compute_s),
            ("memory", memory_s),
            ("collective", collective_s),
            key=lambda kv: kv[1],
        )[0]
        mf = model_flops(cell, n_dev)
        useful = mf / (terms["flops"] * n_dev) if terms["flops"] else 0.0
        result["per_device"] = terms
        result["roofline"] = {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dominant,
            "model_flops": mf,
            "useful_flops_ratio": useful,
            "step_time_lower_bound_s": max(compute_s, memory_s, collective_s),
            "roofline_fraction": (
                compute_s / max(compute_s, memory_s, collective_s)
                if max(compute_s, memory_s, collective_s) > 0
                else 0.0
            ),
        }
    if verbose:
        print(json.dumps(result, indent=2, default=str))
    return result


MESHES = {
    "single_pod": lambda: make_production_mesh(multi_pod=False),
    "multi_pod": lambda: make_production_mesh(multi_pod=True),
    "test": lambda: make_test_mesh(multi_pod=False),
    "test_multi": lambda: make_test_mesh(multi_pod=True),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single_pod", choices=list(MESHES))
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--out", default=None, help="directory for JSON records")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--strategy", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--kv-mode", default=None,
                    choices=[None, "batch", "seq_data", "batch+seq_model", "seq_all"])
    ap.add_argument("--tag", default=None,
                    help="suffix for the output record (perf iterations)")
    ap.add_argument(
        "--override", default=None,
        help="comma list of cfg overrides, e.g. num_heads=48,loss_chunk=512",
    )
    args = ap.parse_args()
    use_compile_cache()

    overrides = None
    if args.override:
        overrides = {}
        for kv in args.override.split(","):
            k, v = kv.split("=")
            overrides[k] = (
                v == "True" if v in ("True", "False") else
                float(v) if "." in v else int(v)
            )

    mesh = MESHES[args.mesh]()
    mesh_name = args.mesh

    if args.out:
        os.makedirs(args.out, exist_ok=True)

    todo = []
    if args.all:
        for arch, shape, reason in all_cells():
            todo.append((arch, shape, reason))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        todo.append((args.arch, args.shape, skip_reason(args.arch, args.shape)))

    failures = []
    for arch, shape, reason in todo:
        tag = f"{arch}__{shape}__{mesh_name}"
        if args.tag:
            tag = f"{tag}__{args.tag}"
        path = os.path.join(args.out, f"{tag}.json") if args.out else None
        if reason is not None:
            rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                   "skipped": reason}
            print(f"SKIP {tag}: {reason}")
        elif args.skip_existing and path and os.path.exists(path):
            print(f"CACHED {tag}")
            continue
        else:
            print(f"=== {tag} ===", flush=True)
            try:
                rec = run_cell(
                    arch, shape, mesh, mesh_name, verbose=not args.out,
                    overrides=overrides, strategy=args.strategy,
                    kv_mode=args.kv_mode,
                )
                r = rec["roofline"]
                print(
                    f"ok   {tag}: compile={rec['compile_s']}s "
                    f"dominant={r['dominant']} "
                    f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                    f"collective={r['collective_s']:.4f}s "
                    f"useful={r['useful_flops_ratio']:.2f}",
                    flush=True,
                )
            except _CELL_ERRORS as e:  # record the cell's failure, continue
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "error": str(e)}
                failures.append(tag)
        if path:
            with open(path, "w") as f:
                json.dump(rec, f, indent=2, default=str)
    if failures:
        print(f"\nFAILED cells: {failures}")
        raise SystemExit(1)
    print("\nall requested cells passed")


if __name__ == "__main__":
    main()
