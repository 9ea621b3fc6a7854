"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract)
and, with ``--json PATH``, also writes the full machine-readable
report so the per-PR bench trajectory (``BENCH_*.json``) can
accumulate across PRs and be gated by ``benchmarks/compare.py``.

  table1  GEMM share of L3 BLAS FLOPs            (paper Table I)
  fig5    BLASX_Malloc vs naive allocator        (paper Fig. 5)
  fig7    throughput + speedup 1/2/3 devices     (paper Fig. 7)
  table3  average parallel efficiency            (paper Table III)
  fig8    heterogeneous load balance             (paper Fig. 8)
  fig10   tile-size sweep                        (paper Fig. 10)
  table4  link model / transfer classes          (paper Table IV)
  table5  communication volume by policy         (paper Table V)
  pallas  TPU tile kernel (interpret) + blocks   (beyond paper)
  context_reuse  warm-context vs per-call H2D    (two-layer API)
  backends       execution backends (numpy/jax/pallas batched dispatch)
  overlap        comm/compute overlap per policy (discrete-event engine)
  autotune       tuned-vs-default config search  (runtime autotuner)
  serving        BlasxServer saturation + tenant isolation (repro.serve)

``--quick`` runs the fast deterministic subset (the CI bench-smoke
lane): table1 + backends + overlap + autotune + serving.
"""
from __future__ import annotations

import argparse
import inspect
import json
import platform
import sys
import time

from . import (autotune, backends, bench_context_reuse, fig5_heap,
               fig7_throughput, fig8_load_balance, fig10_tile_size, overlap,
               pallas_kernel, serving, table1_gemm_fraction,
               table4_link_model, table5_comm_volume)
from .common import rows_to_csv

MODULES = [
    ("table1", table1_gemm_fraction),
    ("fig5", fig5_heap),
    ("fig7+table3", fig7_throughput),
    ("fig8", fig8_load_balance),
    ("fig10", fig10_tile_size),
    ("autotune", autotune),
    ("table4", table4_link_model),
    ("table5", table5_comm_volume),
    ("pallas", pallas_kernel),
    ("context_reuse", bench_context_reuse),
    ("backends", backends),
    ("overlap", overlap),
    ("serving", serving),
]

QUICK_MODULES = [
    ("table1", table1_gemm_fraction),
    ("backends", backends),
    ("overlap", overlap),
    ("autotune", autotune),
    ("serving", serving),
]


def _call_run(mod, quick: bool):
    """Pass quick= through to modules that understand it."""
    fn = mod.run
    try:
        if "quick" in inspect.signature(fn).parameters:
            return fn(quick=quick)
    except (TypeError, ValueError):  # builtins / odd signatures
        pass
    return fn()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run",
        description="BLASX-repro benchmark harness")
    ap.add_argument("--quick", action="store_true",
                    help="fast deterministic subset (CI bench-smoke lane)")
    ap.add_argument("--json", metavar="PATH",
                    help="also write the machine-readable report here")
    ap.add_argument("--only", metavar="LABELS",
                    help="comma-separated module labels to run")
    args = ap.parse_args(argv)

    modules = QUICK_MODULES if args.quick else MODULES
    if args.only:
        wanted = {w.strip() for w in args.only.split(",")}
        available = [label for label, _ in modules]
        modules = [(label, m) for label, m in modules if label in wanted]
        missing = wanted - {label for label, _ in modules}
        if missing:
            lane = "--quick lane" if args.quick else "full lane"
            ap.error(f"module labels {sorted(missing)} not in the "
                     f"{lane} (available: {available})")

    report = {
        "schema": 1,
        "quick": bool(args.quick),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "started_unix": time.time(),
        "results": {},
        "errors": {},
    }
    print("name,us_per_call,derived")
    for label, mod in modules:
        t0 = time.time()
        try:
            rows = _call_run(mod, args.quick)
        except Exception as e:  # keep the harness going; surface the error
            print(f"{label}/ERROR,,{e!r}")
            report["errors"][label] = repr(e)
            continue
        print(rows_to_csv(rows))
        report["results"][label] = rows
        print(f"# {label} done in {time.time()-t0:.1f}s", file=sys.stderr)
    report["elapsed_s"] = time.time() - report["started_unix"]

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=False)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)
    return 1 if report["errors"] else 0


if __name__ == "__main__":
    sys.exit(main())
