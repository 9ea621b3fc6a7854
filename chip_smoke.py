#!/usr/bin/env python3
"""Smoke run of the BLASX main path on a TPU chip.

Drives the library through the entry points a user calls
(``BlasxContext`` routines and ``BlasxServer``) at the paper's sizes,
checks every result against a float64 host reference, and ends with one
JSON line:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One process, in order:

* ``gemm``  — 8192^3 GEMM at tile 1024 (BLASX §V, Fig. 10) on the
  Pallas backend, in float32 and then bfloat16;
* ``chain`` — syrk -> trsm -> gemm at N=4096, float32: the symmetric
  and triangular step groups (jax backend) and the host TRSM solve;
* ``serve`` — a two-lane ``BlasxServer``: two tenants send 8 GEMMs of
  4096^2 activations against a resident 4096^2 weight.

Error is normwise, ``max|C - R| / max|R|``: float32 must reach 1e-5
and bfloat16 output 1e-2.  The times printed are smoke timings — wall
seconds of one cold call (compile included) and one warm call — not a
benchmark.  ``--four-chips`` runs only the distributed ring and GSPMD
GEMM on a 2x2 mesh of four chips against a one-chip reference.

Without a TPU the script exits non-zero; nothing falls back to the CPU.
JAX keeps its compile cache in ``JAX_COMPILATION_CACHE_DIR`` when that
is set, and in ``<repo>/.jax_cache`` otherwise.

Run:  python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent

GEMM_N, TILE = 8192, 1024
CHAIN_N = SERVE_N = 4096
SERVE_REQUESTS = 8
FOUR_CHIP_N = 16384
# normwise error limits: float32 accuracy, and one bfloat16 rounding of
# the output (a single bf16 pass over f32 inputs lands near 1e-3)
TOL = {"float32": 1e-5, "bfloat16": 1e-2}


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        description="Run the BLASX main path once on a TPU and check it "
                    "against float64 host references.")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random inputs (default 0)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed GEMM on a 2x2 mesh of "
                         "four chips")
    return ap.parse_args(argv)


# ------------------------------------------------------------------ helpers
def _np_dtype(name: str) -> np.dtype:
    import ml_dtypes  # registers bfloat16 with numpy; ships with jax

    return np.dtype(ml_dtypes.bfloat16) if name == "bfloat16" \
        else np.dtype(name)


def _inputs(rng, n: int, dtype: str) -> np.ndarray:
    return rng.standard_normal((n, n), dtype=np.float32).astype(
        _np_dtype(dtype))


def _f64(x) -> np.ndarray:
    return np.asarray(x).astype(np.float64)


def normwise_error(got, ref: np.ndarray) -> float:
    return float(np.abs(_f64(got) - ref).max() / np.abs(ref).max())


def _check(label: str, err: float, dtype: str) -> None:
    tol = TOL[dtype]
    print(f"{label}: normwise error {err!r} (limit {tol!r})", flush=True)
    if not err <= tol:  # NaN fails too
        raise AssertionError(f"{label}: error {err!r} above {tol!r}")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _report(result: dict) -> dict:
    print("phase " + json.dumps(result), flush=True)
    return result


def _launch(ctx, label: str) -> dict:
    launch = ctx.stats()["launch"]
    print(f"{label} launch: {json.dumps(launch)}", flush=True)
    return launch


# ------------------------------------------------------------------- phases
def phase_gemm(ctx, n: int, dtype: str, seed: int) -> dict:
    """Two ``ctx.gemm`` calls (cold, warm) on the same n x n inputs."""
    rng = np.random.default_rng(seed)
    a, b = _inputs(rng, n, dtype), _inputs(rng, n, dtype)
    ref = _f64(a) @ _f64(b)
    ctx.reset_stats()
    cold_c, cold = _timed(lambda: ctx.gemm(a, b, dtype=dtype).array())
    warm_c, warm = _timed(lambda: ctx.gemm(a, b, dtype=dtype).array())
    err = max(normwise_error(cold_c, ref), normwise_error(warm_c, ref))
    _check(f"gemm {dtype} n={n}", err, dtype)
    launch = _launch(ctx, f"gemm {dtype}")
    if not launch["engine_flops"].get("pallas", 0) > 0:
        raise AssertionError("gemm: no flops ran on the Pallas kernel")
    return _report({"phase": "gemm", "dtype": dtype, "n": n, "err": err,
                    "smoke_cold_s": cold, "smoke_warm_s": warm,
                    "launch": launch})


def phase_chain(ctx, n: int, seed: int) -> dict:
    """S = syrk(A) (lower), X = tril(S)^-1 B, Y = X @ W in float32."""
    import scipy.linalg

    dtype = "float32"
    rng = np.random.default_rng(seed)
    a, b, w = (_inputs(rng, n, dtype) for _ in range(3))
    s_ref = np.tril(_f64(a) @ _f64(a).T)
    ref = scipy.linalg.solve_triangular(s_ref, _f64(b), lower=True) @ _f64(w)

    def chain():
        s = ctx.syrk(a, uplo="L", dtype=dtype)
        x = ctx.trsm(s, b, uplo="L", dtype=dtype)
        return ctx.gemm(x, w, dtype=dtype).array()

    ctx.reset_stats()
    cold_y, cold = _timed(chain)
    warm_y, warm = _timed(chain)
    err = max(normwise_error(cold_y, ref), normwise_error(warm_y, ref))
    _check(f"chain syrk->trsm->gemm {dtype} n={n}", err, dtype)
    launch = _launch(ctx, "chain")
    return _report({"phase": "chain", "dtype": dtype, "n": n, "err": err,
                    "smoke_cold_s": cold, "smoke_warm_s": warm,
                    "launch": launch})


def phase_serve(config, tile: int, n: int, seed: int,
                n_requests: int = SERVE_REQUESTS) -> dict:
    """Two tenants, each on its own lane of a two-context server, send
    ``n_requests`` GEMMs in total against one weight held resident on
    each tenant's lane.  The first request runs alone (cold)."""
    from repro.api import BlasxContext
    from repro.serve import BlasxServer

    dtype = "float32"
    rng = np.random.default_rng(seed)
    w = _inputs(rng, n, dtype)
    xs = [_inputs(rng, n, dtype) for _ in range(n_requests)]
    tenants = ("tenant-a", "tenant-b")
    with contextlib.ExitStack() as stack:
        ctxs = [stack.enter_context(BlasxContext(config, tile=tile))
                for _ in range(2)]
        srv = stack.enter_context(BlasxServer(contexts=ctxs))
        weights = {t: srv.tile(t, w) for t in tenants}
        lanes = {srv.context_of(t) for t in tenants}
        if srv.pool_size != 2 or lanes != {0, 1}:
            raise AssertionError(f"serve: tenants share a lane: {lanes}")

        def send(i):
            t = tenants[i % 2]
            return srv.submit(t, "gemm", xs[i], weights[t], dtype=dtype)

        first, cold = _timed(lambda: send(0).result().array())
        rest, warm = _timed(lambda: [f.result().array() for f in
                                     [send(i) for i in range(1, n_requests)]])
        stats = srv.stats()["tenants"]
        # read before the contexts close: closing drops their ledgers
        launch = [_launch(c, f"serve lane {i}") for i, c in enumerate(ctxs)]
    outs = [first] + rest
    w64 = _f64(w)
    err = max(normwise_error(y, _f64(x) @ w64) for x, y in zip(xs, outs))
    _check(f"serve {n_requests} gemm requests {dtype} n={n}", err, dtype)
    done = sum(s["completed"] for s in stats.values())
    failed = sum(s["failed"] for s in stats.values())
    if done != n_requests or failed:
        raise AssertionError(f"serve: {done} completed, {failed} failed")
    return _report({"phase": "serve", "dtype": dtype, "n": n,
                    "requests": n_requests, "err": err,
                    "smoke_cold_s": cold, "smoke_warm_s": warm,
                    "launch": launch})


def phase_four_chips(devices, n: int, seed: int) -> dict:
    """``distributed_gemm`` ring and GSPMD on a 2x2 mesh, bfloat16,
    against an unsharded f32-accumulated ``jnp.dot`` on one chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed import distributed_gemm

    if len(devices) < 4:
        raise AssertionError(f"four-chip phase needs 4 devices, "
                             f"got {len(devices)}")
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "model"))
    ka, kb = jax.random.split(jax.random.key(seed))

    def normal(key, spec):
        return jax.jit(
            lambda k: jax.random.normal(k, (n, n), jnp.bfloat16),
            out_shardings=NamedSharding(mesh, spec))(key)

    a, b = normal(ka, P("data", "model")), normal(kb, P("model", None))
    one = devices[0]
    ref = jnp.dot(jax.device_put(a, one), jax.device_put(b, one),
                  preferred_element_type=jnp.float32)

    @jax.jit
    def err_of(c, r):
        return (jnp.max(jnp.abs(c.astype(jnp.float32) - r))
                / jnp.max(jnp.abs(r)))

    result = {"phase": "four_chips", "dtype": "bfloat16", "n": n}
    for mode in ("ring", "gspmd"):
        fn = jax.jit(lambda x, y, m=mode: distributed_gemm(x, y, mesh,
                                                           mode=m))
        compiled, compile_s = _timed(lambda: fn.lower(a, b).compile())
        c, cold = _timed(lambda: compiled(a, b).block_until_ready())
        c, warm = _timed(lambda: compiled(a, b).block_until_ready())
        n_dev = len(c.sharding.device_set)
        if n_dev != 4:
            raise AssertionError(f"{mode}: output on {n_dev} devices")
        n_perm = compiled.as_text().count("collective-permute")
        if mode == "ring" and n_perm == 0:
            raise AssertionError("ring: no collective-permute in the HLO")
        err = float(err_of(jax.device_put(c, one), ref))
        _check(f"distributed_gemm {mode} bfloat16 n={n}", err, "bfloat16")
        result[mode] = {"err": err, "devices": n_dev,
                        "collective_permutes": n_perm,
                        "smoke_compile_s": compile_s,
                        "smoke_cold_s": cold, "smoke_warm_s": warm}
    return _report(result)


def pallas_lowers_to_mosaic(tile: int, steps: int, dtype: str) -> bool:
    """Compile the Pallas step-group kernel for the default device and
    report whether it became a Mosaic custom call (interpret mode would
    lower to plain XLA ops instead)."""
    import jax

    from repro.backends.pallas_backend import _batched_pallas_contract

    fn = _batched_pallas_contract(steps, tile, tile, tile, dtype, False)
    arg = jax.ShapeDtypeStruct((4, steps, tile, tile), _np_dtype(dtype))
    return "tpu_custom_call" in fn.lower(arg, arg).compile().as_text()


# --------------------------------------------------------------------- main
def _place_compile_cache(jax) -> None:
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; only without it does
    the cache go to a fixed path in the checkout (the path is part of
    the cache key, so it never moves)."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (first device {dev.platform!r}, default "
              f"backend {jax.default_backend()!r}); this script does not "
              "run on the CPU", file=sys.stderr)
        return 2
    print(f"device_kind={dev.device_kind!r} device_count={len(devices)} "
          f"jax={jax.__version__}", flush=True)
    _place_compile_cache(jax)
    sys.path.insert(0, str(REPO / "src"))
    from repro.api import BlasxContext
    from repro.core.runtime import RuntimeConfig

    if args.four_chips:
        phase_four_chips(devices, FOUR_CHIP_N, args.seed)
    else:
        if not pallas_lowers_to_mosaic(TILE, GEMM_N // TILE, "float32"):
            raise AssertionError("the Pallas group kernel did not lower "
                                 "to a Mosaic tpu_custom_call")
        print("pallas group kernel: tpu_custom_call present", flush=True)
        cfg = RuntimeConfig(n_devices=1, mode="sim", backend="pallas")
        with BlasxContext(cfg, tile=TILE) as ctx:
            for dtype in ("float32", "bfloat16"):
                phase_gemm(ctx, GEMM_N, dtype, args.seed)
            phase_chain(ctx, CHAIN_N, args.seed)
        phase_serve(cfg, TILE, SERVE_N, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
