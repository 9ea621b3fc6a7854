"""Bench regression gate: compare a BENCH_*.json report to the
committed baseline.

Usage (the CI bench-smoke lane)::

    python -m benchmarks.run --quick --json BENCH_pr.json
    python benchmarks/compare.py --baseline benchmarks/baseline.json \
        BENCH_pr.json

Two classes of checks:

* **Invariants** — absolute properties of the PR report that must hold
  on any machine: the batched JaxBackend beats the per-step
  NumpyBackend wall-clock on the quick GEMM benchmark, issues
  strictly fewer kernel launches than scheduled tile tasks, the
  SGEMM lane (float32 storage) is at least as fast as the DGEMM lane
  on the jax backend (half the cache/stage bytes, no f64->f32 staging
  cast — see benchmarks/backends.py), the discrete-event overlap
  lane's structural properties hold (overlap-on makespan <=
  overlap-off on every policy; blasx COMM fraction <= cublasxt;
  work-centric Stream-K scheduling strictly improves both makespan
  and overlap efficiency on every deep-k ragged shape of the ragged
  sub-lane — see benchmarks/overlap.py), the runtime-autotuner
  lane's properties
  hold (tuned makespan <= default on every routine x dtype; the second
  tuning pass is a pure cache hit; on the long-tailed fresh shape
  distribution the learned-cost-model ``auto`` mode pays >= 5x fewer
  shadow runs than a full sweep while every adopted config is still
  measured tuned <= default — see benchmarks/autotune.py), and
  the serving lane's flags hold (quota'd tenant isolation + its
  fails-without counterpart, exact admission rejections, interactive
  before batch, loaded-vs-unloaded p99 bound — see
  benchmarks/serving.py).
* **Regressions vs baseline** — metrics compared against
  ``benchmarks/baseline.json`` with a tolerance (default 20%; CI
  passes 35%): the jax-vs-numpy speedup ratio and the deterministic
  kernel-launch/launches-saved counts.  The speedup is a within-run
  ratio so absolute host speed cancels, but the OpenBLAS-vs-XLA
  *relative* speed still varies by host and carries ~15% run-to-run
  noise — hence the widened CI tolerance and a committed baseline
  taken from the conservative end of several runs; the invariant
  above is the hard floor.  Raw GFLOP/s are *recorded* in the report
  for the trajectory but not gated by default — the committed
  baseline and the CI runner are different machines
  (``--gate-gflops`` opts in when comparing like-for-like hosts).

Exits non-zero with a line per violation.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List


def _rows_by_name(report: dict) -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    for rows in report.get("results", {}).values():
        for row in rows:
            out[row["name"]] = row
    return out


def _num(row: dict, key: str):
    try:
        return float(row[key])
    except (KeyError, TypeError, ValueError):
        return None


class Gate:
    def __init__(self):
        self.failures: List[str] = []
        self.notes: List[str] = []

    def fail(self, msg: str) -> None:
        self.failures.append(msg)

    def note(self, msg: str) -> None:
        self.notes.append(msg)

    def check_ratio(self, name: str, metric: str, pr, base, tol: float,
                    higher_is_better: bool) -> None:
        if pr is None or base is None:
            self.fail(f"{name}: metric {metric!r} missing "
                      f"(pr={pr}, baseline={base})")
            return
        if base == 0:
            self.note(f"{name}.{metric}: baseline is 0, skipping ratio")
            return
        ratio = pr / base
        ok = ratio >= (1 - tol) if higher_is_better else ratio <= (1 + tol)
        arrow = "↑" if higher_is_better else "↓"
        line = (f"{name}.{metric} ({arrow} better): pr={pr:g} "
                f"baseline={base:g} ratio={ratio:.3f} tol={tol:.0%}")
        if ok:
            self.note("OK   " + line)
        else:
            self.fail("FAIL " + line)


def check_invariants(gate: Gate, pr_rows: Dict[str, dict]) -> None:
    summary = pr_rows.get("backends/summary")
    if summary is None:
        gate.fail("backends/summary row missing from PR report")
        return
    if _num(summary, "jax_beats_numpy") != 1:
        gate.fail(
            "invariant: batched JaxBackend must beat NumpyBackend "
            f"wall-clock on the quick GEMM benchmark "
            f"(speedup={summary.get('jax_speedup_vs_numpy')})")
    else:
        gate.note(f"OK   invariant: jax beats numpy "
                  f"(speedup={summary.get('jax_speedup_vs_numpy')}x)")
    if _num(summary, "jax_fewer_launches_than_tasks") != 1:
        gate.fail(
            "invariant: JaxBackend must issue fewer kernel launches "
            f"than scheduled tile tasks "
            f"(launches={summary.get('jax_launches')}, "
            f"tasks={summary.get('jax_tasks')})")
    else:
        gate.note(f"OK   invariant: jax launches "
                  f"{summary.get('jax_launches')} < tasks "
                  f"{summary.get('jax_tasks')}")
    if _num(summary, "jax_f32_ge_f64") != 1:
        gate.fail(
            "invariant: the SGEMM lane (float32 storage) must be at "
            "least as fast as DGEMM on the jax backend (10% noise floor; "
            f"f32 speedup={summary.get('jax_f32_speedup_vs_f64')})")
    else:
        gate.note(f"OK   invariant: jax f32 >= f64 wall-clock "
                  f"(speedup={summary.get('jax_f32_speedup_vs_f64')}x)")
    check_overlap_invariants(gate, pr_rows)
    check_autotune_invariants(gate, pr_rows)
    check_serving_invariants(gate, pr_rows)


def check_overlap_invariants(gate: Gate, pr_rows: Dict[str, dict]) -> None:
    """Structural properties of the discrete-event overlap lane.

    Virtual-clock metrics are deterministic and host-independent, so
    these are hard invariants: letting communication overlap compute
    can never *lengthen* the modeled makespan, and the cached
    4-stream blasx schedule must not have a worse Fig. 8 COMM
    fraction than the uncached 2-stream cublasxt one."""
    summary = pr_rows.get("overlap/summary")
    if summary is None:
        gate.fail("overlap/summary row missing from PR report")
        return
    if _num(summary, "overlap_le_off_all") != 1:
        bad = [name for name, row in pr_rows.items()
               if name.startswith("overlap/")
               and _num(row, "overlap_le_off") == 0]
        gate.fail("invariant: overlap-on makespan must be <= overlap-off "
                  f"on every policy (violated by: {bad})")
    else:
        gate.note("OK   invariant: overlap-on makespan <= overlap-off "
                  "on every policy")
    if _num(summary, "blasx_comm_le_cublasxt") != 1:
        gate.fail(
            "invariant: blasx COMM fraction must be <= cublasxt "
            f"(blasx={summary.get('blasx_comm_fraction')}, "
            f"cublasxt={summary.get('cublasxt_comm_fraction')})")
    else:
        gate.note(f"OK   invariant: blasx COMM fraction "
                  f"{summary.get('blasx_comm_fraction')} <= cublasxt "
                  f"{summary.get('cublasxt_comm_fraction')}")
    ragged = pr_rows.get("overlap/ragged_summary")
    if ragged is None:
        gate.fail("overlap/ragged_summary row missing from PR report")
        return
    if _num(ragged, "work_centric_improves_all") != 1:
        bad = [name for name, row in pr_rows.items()
               if name.startswith("overlap/ragged_")
               and name != "overlap/ragged_summary"
               and _num(row, "wc_improves") == 0]
        gate.fail(
            "invariant: work-centric scheduling must strictly improve "
            "both makespan and overlap_efficiency on every deep-k "
            f"ragged shape (violated by: {bad})")
    else:
        gate.note("OK   invariant: work-centric improves makespan AND "
                  "overlap_efficiency on every ragged shape")


def check_autotune_invariants(gate: Gate, pr_rows: Dict[str, dict]) -> None:
    """Structural properties of the runtime-autotuner lane (virtual
    clock, deterministic): the tuned config's makespan never exceeds
    the fixed default's on any routine x dtype (the default is always
    candidate zero of the sweep), and a second tuner over the same
    cache performs ZERO shadow runs — every later context starts warm."""
    summary = pr_rows.get("autotune/summary")
    if summary is None:
        gate.fail("autotune/summary row missing from PR report")
        return
    if _num(summary, "tuned_le_default_all") != 1:
        bad = [name for name, row in pr_rows.items()
               if name.startswith("autotune/")
               and _num(row, "tuned_le_default") == 0]
        gate.fail("invariant: tuned makespan must be <= default makespan "
                  f"on every routine x dtype (violated by: {bad})")
    else:
        gate.note("OK   invariant: tuned makespan <= default on every "
                  "routine x dtype")
    if _num(summary, "second_pass_pure_cache_hit") != 1:
        gate.fail(
            "invariant: the second tuning pass must be a pure cache hit "
            f"(second_pass_sweeps={summary.get('second_pass_sweeps')})")
    else:
        gate.note(f"OK   invariant: second tuning pass swept 0 configs "
                  f"({summary.get('cache_entries')} cached entries)")
    longtail = pr_rows.get("autotune/longtail")
    if longtail is None:
        gate.fail("autotune/longtail row missing from PR report")
        return
    if _num(longtail, "tuned_le_default_all") != 1:
        gate.fail(
            "invariant: every config the auto-mode tuner adopts on the "
            "long-tailed fresh distribution must satisfy measured tuned "
            "makespan <= default")
    else:
        gate.note("OK   invariant: longtail tuned <= default on all "
                  f"{longtail.get('fresh_buckets')} fresh buckets")
    if _num(longtail, "sweep_reduction_ge_5x") != 1:
        gate.fail(
            "invariant: auto mode must pay >= 5x fewer shadow runs than "
            "sweep mode on the fresh long-tailed distribution "
            f"(sweep_mode_runs={longtail.get('sweep_mode_runs')}, "
            f"auto_mode_runs={longtail.get('auto_mode_runs')}, "
            f"reduction={longtail.get('sweep_reduction')}x)")
    else:
        gate.note(f"OK   invariant: longtail sweep reduction "
                  f"{longtail.get('sweep_reduction')}x >= 5x "
                  f"({longtail.get('model_adoptions')} model adoptions, "
                  f"{longtail.get('model_fallbacks')} fallbacks)")


def check_serving_invariants(gate: Gate, pr_rows: Dict[str, dict]) -> None:
    """Structural properties of the serving lane (benchmarks/serving.py).

    The isolation/admission flags are deterministic (sim mode, fixed
    seeds, single worker per context): a quota'd flood must leave the
    other tenant's warm tile set untouched, the identical flood
    without quotas must evict it (the fails-without-feature
    counterpart), admission must shed exactly offered-minus-capacity
    requests, and a queued interactive request must complete before a
    queued batch one.  The wall-clock latency row is gated only
    through its in-lane ``latency_isolation_ok`` flag — tenant B's
    loaded p99 must stay within a generous ratio of its unloaded p99
    while tenant A saturates the pool (host speed cancels)."""
    summary = pr_rows.get("serving/summary")
    if summary is None:
        gate.fail("serving/summary row missing from PR report")
        return
    checks = (
        ("isolation_ok",
         "a quota'd flood must not evict the other tenant's warm set"),
        ("flood_evicts_without_quota",
         "without quotas the same flood must evict the warm set "
         "(fails-without-feature counterpart)"),
        ("rejections_exact",
         "admission must reject exactly offered-minus-capacity "
         "requests at the depth bound"),
        ("interactive_first",
         "a queued interactive request must complete before a queued "
         "batch request"),
        ("latency_isolation_ok",
         "tenant B's p99 under tenant A's flood must stay within the "
         "gated ratio of its unloaded p99"),
    )
    for flag, what in checks:
        if _num(summary, flag) != 1:
            gate.fail(f"invariant: {what} (serving/summary.{flag}="
                      f"{summary.get(flag)})")
        else:
            gate.note(f"OK   invariant: serving {flag}")


def check_regressions(gate: Gate, pr_rows: Dict[str, dict],
                      base_rows: Dict[str, dict], tol: float,
                      gate_gflops: bool) -> None:
    def both(name):
        pr, base = pr_rows.get(name), base_rows.get(name)
        if pr is None or base is None:
            gate.fail(f"row {name!r} missing "
                      f"(pr={'yes' if pr else 'no'}, "
                      f"baseline={'yes' if base else 'no'})")
            return None, None
        return pr, base

    pr, base = both("backends/summary")
    if pr is not None:
        gate.check_ratio("backends/summary", "jax_speedup_vs_numpy",
                         _num(pr, "jax_speedup_vs_numpy"),
                         _num(base, "jax_speedup_vs_numpy"),
                         tol, higher_is_better=True)
        gate.check_ratio("backends/summary", "jax_f32_speedup_vs_f64",
                         _num(pr, "jax_f32_speedup_vs_f64"),
                         _num(base, "jax_f32_speedup_vs_f64"),
                         tol, higher_is_better=True)
    for name in ("backends/gemm_numpy", "backends/gemm_jax",
                 "backends/gemm_numpy_f32", "backends/gemm_jax_f32"):
        pr, base = both(name)
        if pr is None:
            continue
        gate.check_ratio(name, "kernel_launches",
                         _num(pr, "kernel_launches"),
                         _num(base, "kernel_launches"),
                         tol, higher_is_better=False)
        gate.check_ratio(name, "launches_saved",
                         _num(pr, "launches_saved"),
                         _num(base, "launches_saved"),
                         tol, higher_is_better=True)
        if gate_gflops:
            gate.check_ratio(name, "gflops", _num(pr, "gflops"),
                             _num(base, "gflops"), tol,
                             higher_is_better=True)
    # overlap lane: virtual-clock metrics, deterministic across hosts
    for name in ("overlap/blasx", "overlap/parsec", "overlap/static",
                 "overlap/cublasxt"):
        pr, base = both(name)
        if pr is None:
            continue
        gate.check_ratio(name, "comm_fraction",
                         _num(pr, "comm_fraction"),
                         _num(base, "comm_fraction"),
                         tol, higher_is_better=False)
        gate.check_ratio(name, "overlap_efficiency",
                         _num(pr, "overlap_efficiency"),
                         _num(base, "overlap_efficiency"),
                         tol, higher_is_better=True)
        gate.check_ratio(name, "makespan_on",
                         _num(pr, "makespan_on"),
                         _num(base, "makespan_on"),
                         tol, higher_is_better=False)
    # ragged sub-lane: deep-k work-centric rows, also virtual-clock
    ragged = sorted(name for name in (set(pr_rows) | set(base_rows))
                    if name.startswith("overlap/ragged_")
                    and name != "overlap/ragged_summary")
    for name in ragged:
        pr, base = both(name)
        if pr is None:
            continue
        gate.check_ratio(name, "makespan_wc",
                         _num(pr, "makespan_wc"),
                         _num(base, "makespan_wc"),
                         tol, higher_is_better=False)
        gate.check_ratio(name, "wc_speedup",
                         _num(pr, "wc_speedup"),
                         _num(base, "wc_speedup"),
                         tol, higher_is_better=True)
    # autotune lane: virtual-clock metrics, deterministic across hosts
    for routine in ("gemm", "syrk", "syr2k", "symm", "trmm", "trsm"):
        for prec in ("f64", "f32"):
            name = f"autotune/{routine}_{prec}"
            pr, base = both(name)
            if pr is None:
                continue
            gate.check_ratio(name, "tuned_makespan",
                             _num(pr, "tuned_makespan"),
                             _num(base, "tuned_makespan"),
                             tol, higher_is_better=False)
            gate.check_ratio(name, "default_makespan",
                             _num(pr, "default_makespan"),
                             _num(base, "default_makespan"),
                             tol, higher_is_better=False)
    # longtail sub-lane: shadow-run counts are deterministic (virtual
    # clock + fixed shape distributions), so a shrinking reduction is a
    # real model/search regression, not noise
    pr, base = both("autotune/longtail")
    if pr is not None:
        gate.check_ratio("autotune/longtail", "sweep_reduction",
                         _num(pr, "sweep_reduction"),
                         _num(base, "sweep_reduction"),
                         tol, higher_is_better=True)
        gate.check_ratio("autotune/longtail", "auto_mode_runs",
                         _num(pr, "auto_mode_runs"),
                         _num(base, "auto_mode_runs"),
                         tol, higher_is_better=False)
    # serving lane: deterministic tile/eviction/rejection counts (sim
    # mode, fixed seeds); the wall-clock latency row is NOT gated here
    pr, base = both("serving/isolation")
    if pr is not None:
        gate.check_ratio("serving/isolation", "warm_tiles_after",
                         _num(pr, "warm_tiles_after"),
                         _num(base, "warm_tiles_after"),
                         tol, higher_is_better=True)
        gate.check_ratio("serving/isolation", "quota_evictions",
                         _num(pr, "quota_evictions"),
                         _num(base, "quota_evictions"),
                         tol, higher_is_better=False)
    pr, base = both("serving/admission")
    if pr is not None:
        gate.check_ratio("serving/admission", "rejected",
                         _num(pr, "rejected"), _num(base, "rejected"),
                         tol, higher_is_better=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("report", help="BENCH_*.json produced by "
                                   "`python -m benchmarks.run --json`")
    ap.add_argument("--baseline", required=True,
                    help="committed baseline report")
    ap.add_argument("--tolerance", type=float, default=0.20,
                    help="allowed relative regression (default 0.20)")
    ap.add_argument("--gate-gflops", action="store_true",
                    help="also gate raw GFLOP/s (like-for-like hosts only)")
    ap.add_argument("--no-invariants", action="store_true",
                    help="skip absolute invariant checks")
    args = ap.parse_args(argv)

    with open(args.report) as f:
        pr_rows = _rows_by_name(json.load(f))
    with open(args.baseline) as f:
        base_rows = _rows_by_name(json.load(f))

    gate = Gate()
    if not args.no_invariants:
        check_invariants(gate, pr_rows)
    check_regressions(gate, pr_rows, base_rows, args.tolerance,
                      args.gate_gflops)

    for line in gate.notes:
        print(line)
    for line in gate.failures:
        print(line, file=sys.stderr)
    if gate.failures:
        print(f"\n{len(gate.failures)} bench gate violation(s)",
              file=sys.stderr)
        return 1
    print("\nbench gate: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
