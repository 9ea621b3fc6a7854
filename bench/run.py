#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on a TPU and print one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: parse the arguments, refuse any device that is not a TPU
(nothing falls back to the CPU), build the cell's inputs from the seed,
warm up every shape the window uses (set-up), measure for ``--seconds``,
compare what the window produced with the float64 reference, and print
``{"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
"checks"}`` as the last line of standard output, each compared number
beside its limit also as the last lines of standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the window under the profiler, with the benchmark's spans on, and
reports its per-layer metrics.  Traces go to ``bench_traces/<cell>``.
JAX keeps its compile cache in ``JAX_COMPILATION_CACHE_DIR`` when that is
set, and in ``<checkout>/.jax_cache`` otherwise.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# run as a script, the interpreter put bench/ first on the path, where
# its modules would shadow standard ones (trace, window): import the
# package from the checkout's root instead
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path.pop(0)
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check  # noqa: E402
from bench.cell import (Cell, UnknownWorkload, find_cell, load_peaks,  # noqa: E402
                        loop_class, metric_reader)
from bench.spans import CompileCounter, Spans  # noqa: E402


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True,
                    help="a cell name of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


@dataclasses.dataclass
class Record:
    """What the per-layer readers (``bench/metrics``) read."""
    window_s: float
    spans: Optional[Spans]
    groups: int
    compiles: int
    trace: object = None
    extra: dict = dataclasses.field(default_factory=dict)


def _groups(ctxs) -> int:
    return sum(c.runtime.launch_stats()["groups"] for c in ctxs)


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             peaks: dict, t_start: float,
             trace_dir: Optional[Path] = None) -> dict:
    """Set up, measure, check; the result line as a dict."""
    import jax

    spans = Spans(annotate=True) if trace else None
    loop = loop_class(cell)(cell, seed, spans)
    counter = CompileCounter()
    try:
        loop.warm()
        groups0 = _groups(loop.contexts)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        setup_s = time.perf_counter() - t_start
        if spans is not None:
            spans.reset()
        counter.arm()
        with (spans or Spans(annotate=False)).window():
            out = loop.window(seconds)
        counter.disarm()
        if trace:
            jax.profiler.stop_trace()
        groups = _groups(loop.contexts) - groups0
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": _peak_bytes(dev)}
    finally:
        counter.close()
        loop.close()

    # the reference runs once the window has closed and the peak is read
    correct, checks = check.verdict(loop.numbers(out), cell.limits)
    metrics = dict(out.metrics, setup_s=setup_s)
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed}
    if trace:
        from bench import trace as tr

        summary = tr.summarize(tr.load(tr.find_xplane(str(trace_dir))), peaks)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        if summary.roofline is not None:
            print(f"matmul roofline: {summary.roofline['bound']}-bound, "
                  f"kernel time {summary.roofline['kernel_s']!r} s",
                  file=sys.stderr)
        rec = Record(window_s=out.window_s, spans=spans, groups=groups,
                     compiles=counter.count, trace=summary, extra=out.extra)
        result["metrics"] = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(rec)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        result["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    if trace:
        result["breakdown"] = summary.breakdown
    result["checks"] = checks
    return result


def _place_compile_cache(jax) -> None:
    """A fixed path in the checkout unless JAX_COMPILATION_CACHE_DIR is
    set (JAX reads that itself); every program is cached."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = find_cell(args.workload)
    except UnknownWorkload as e:
        print(f"bench: {e.args[0]}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (first device is {devices[0].platform!r}); "
              "the benchmark does not run on the CPU", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    try:
        peaks = load_peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"bench: {e.args[0]}", file=sys.stderr)
        return 2
    _place_compile_cache(jax)
    sys.path.insert(0, str(ROOT / "src"))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks,
                      T_START, ROOT / "bench_traces" / cell.name)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
