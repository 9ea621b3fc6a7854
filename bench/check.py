"""The comparison that decides ``correct``, and its lower-precision control.

What is compared is what the timed window produced: every call's
output, on rows drawn from the seed so that every row block of tiles is
covered, against the float64 reference of
``bench/routines`` computed on the host after the window.  A number is
the worst normwise error, ``max|got - ref| / max|ref|``, over all of
them; each number has its own limit (``bench/limits/<cell>.json``).

The control is the same reference with every product in float32 at
``high`` (three bf16 passes, the step below the ``highest`` the
configurations state): ``bf16x3`` below, on the host with numpy or on
the chip with jax.  Both split each float32 operand into a high and a
low bfloat16 part by masking mantissa bits, which no compiler folds
away, and drop the low-by-low product, as the three-pass mode does.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import numpy as np

_HIGH_BITS = np.uint32(0xFFFF0000)


def sample_rows(n: int, block: int, per_block: int,
                rng: np.random.Generator) -> np.ndarray:
    """``per_block`` rows drawn from each ``block`` rows of ``n``, sorted."""
    out = []
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        out.append(rng.choice(np.arange(lo, hi), size=min(per_block, hi - lo),
                              replace=False))
    return np.sort(np.concatenate(out))


def normwise(got, ref) -> float:
    got = np.asarray(got, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    scale = float(np.abs(ref).max()) if ref.size else 0.0
    diff = float(np.abs(got - ref).max()) if ref.size else 0.0
    return diff / scale if scale > 0 else diff


def _split_np(x: np.ndarray):
    x = np.ascontiguousarray(x, dtype=np.float32)
    hi = (x.view(np.uint32) & _HIGH_BITS).view(np.float32)
    rest = x - hi
    lo = (rest.view(np.uint32) & _HIGH_BITS).view(np.float32)
    return hi, lo


def bf16x3_np(a, b) -> np.ndarray:
    """float32 product at three bf16 passes, on the host."""
    ah, al = _split_np(a)
    bh, bl = _split_np(b)
    return ah @ bh + (ah @ bl + al @ bh)


@functools.lru_cache(maxsize=None)
def _bf16x3_jit():
    import jax
    import jax.numpy as jnp

    def split(v):
        hi = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(v, jnp.uint32)
            & jnp.uint32(0xFFFF0000), jnp.float32)
        lo = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(v - hi, jnp.uint32)
            & jnp.uint32(0xFFFF0000), jnp.float32)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    def d(p, q):
        return jnp.matmul(p, q, preferred_element_type=jnp.float32)

    @jax.jit
    def run(x, y):
        xh, xl = split(x)
        yh, yl = split(y)
        return d(xh, yh) + (d(xh, yl) + d(xl, yh))

    return run


def bf16x3_jax(a, b) -> np.ndarray:
    """float32 product at three bf16 passes, on the default device."""
    import jax.numpy as jnp

    return np.asarray(_bf16x3_jit()(jnp.asarray(a, jnp.float32),
                                    jnp.asarray(b, jnp.float32)))


def verdict(values: Dict[str, float], limits: Dict[str, float]):
    """``(correct, checks)``: each number beside its limit.  A number
    above its limit, or not a number, fails."""
    checks = {k: {"value": float(values[k]), "limit": float(limits[k])}
              for k in limits}
    ok = all(not math.isnan(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
