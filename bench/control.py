#!/usr/bin/env python3
"""Readings that set a cell's limits (``bench/limits/<cell>.json``).

    python bench/control.py --workload n8192.gemm --seeds 1,2,3 --seconds 0

For each seed, in one process: the program's numbers from a short window
through the cell's own timed path (the lower readings), and the control's:
the reference put in the program's place, with every product in float32
at ``high`` (three bf16 passes), compared by the same numbers (the upper
readings).  On a TPU the control is read three ways: the three-pass
product written out (``bf16x3``), XLA's own ``Precision.HIGH``, and the
default single bf16 pass.  One JSON line per seed.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check  # noqa: E402
from bench.cell import Cell, find_cell, loop_class  # noqa: E402


def products(on_device: bool) -> dict:
    """The control's products, by name."""
    if not on_device:
        return {"bf16x3": check.bf16x3_np}
    import jax.numpy as jnp

    def xla(precision):
        def mm(a, b):
            return np.asarray(jnp.matmul(jnp.asarray(a, jnp.float32),
                                         jnp.asarray(b, jnp.float32),
                                         precision=precision))
        return mm

    return {"bf16x3": check.bf16x3_jax, "xla_high": xla("high"),
            "xla_default": xla("default")}


def readings(cell: Cell, seed: int, seconds: float, mms: dict) -> dict:
    """Program and control numbers of one seed."""
    loop = loop_class(cell)(cell, seed)
    try:
        out = loop.window(seconds)
    finally:
        loop.close()
    return {"seed": seed, "attempted": out.attempted,
            "program": loop.numbers(out),
            "control": {name: loop.control_numbers(out, mm)
                        for name, mm in mms.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window of the program's readings (closed loops "
                         "run at least one call)")
    args = ap.parse_args(argv)
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        from bench.run import _place_compile_cache

        _place_compile_cache(jax)
    cell = find_cell(args.workload)
    mms = products(on_tpu)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, mms)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
