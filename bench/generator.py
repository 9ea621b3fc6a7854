"""What every traffic loop (``bench/loops/<loop>.py``) shares: seeded
host inputs, sizes read from the configuration, the library's context,
and the check that a mix or a configuration holds no key that nothing
reads.

Sizes in a mix are expressions over the configuration's whole numbers
(``"n - tile"``).  Inputs are made on the host from ``--seed`` (the
library takes host arrays), in the configuration's dtype.
"""
from __future__ import annotations

import ast
import math
import operator
from typing import Dict, Iterable, Optional

import numpy as np

from .spans import Spans, instrument

# rows drawn per tile-row block of a compared output
ROWS_PER_BLOCK = 8
# the precision the library issues float32 products at (PR 11's finding:
# the chip's default is a single bf16 pass); a configuration that states
# another has no path to run
PRECISION = "highest"
# what a configuration holds besides its whole-number sizes; everything
# but ``runtime``, ``tile``, ``dtype`` and ``precision`` documents it
CONFIG_KEYS = {"name", "source", "deployment", "reduced", "reduced_why",
               "assumed", "tile", "dtype", "precision", "runtime"}

_OPS = {ast.Add: operator.add, ast.Sub: operator.sub,
        ast.Mult: operator.mul, ast.FloorDiv: operator.floordiv}


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), *tags])


def check_keys(doc: dict, allowed: Iterable[str], where: str) -> None:
    """Refuse a key that nothing reads: it would be an option that does
    nothing."""
    extra = sorted(set(doc) - set(allowed))
    if extra:
        raise ValueError(f"{where}: unknown keys {extra} (read: "
                         f"{sorted(allowed)})")


def config_sizes(config: dict) -> Dict[str, int]:
    """The configuration's whole numbers, after refusing unread keys and
    a precision the library does not run."""
    sizes = {k: v for k, v in config.items()
             if isinstance(v, int) and not isinstance(v, bool)}
    check_keys(config, CONFIG_KEYS | set(sizes),
               f"configuration {config.get('name')!r}")
    if config["precision"] != PRECISION:
        raise ValueError(f"configuration {config['name']!r} states precision "
                         f"{config['precision']!r}; the library issues "
                         f"float32 products at {PRECISION!r} only")
    return sizes


def size(expr, env: Dict[str, int]) -> int:
    """A whole number or an expression of +, -, *, // over ``env``."""
    if isinstance(expr, int):
        return expr

    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return node.value
        if isinstance(node, ast.Name):
            return env[node.id]
        if isinstance(node, ast.BinOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](ev(node.left), ev(node.right))
        raise ValueError(f"size expression {expr!r}: unsupported syntax")

    return int(ev(ast.parse(expr, mode="eval")))


def make(spec: dict, env: Dict[str, int], gen: np.random.Generator,
         dtype) -> np.ndarray:
    """A seeded host matrix: ``normal`` entries, a ``symmetric`` matrix,
    or the lower ``cholesky_factor`` of G G^T / n + I (well conditioned)."""
    check_keys(spec, ("shape", "fill"), "operand")
    shape = tuple(size(s, env) for s in spec["shape"])
    fill = spec["fill"]
    x = gen.standard_normal(shape, dtype=np.float32)
    if fill == "normal":
        pass
    elif fill == "symmetric":
        x = (x + x.T) * np.float32(0.5)
    elif fill == "cholesky_factor":
        g = x.astype(np.float64) / math.sqrt(shape[1])
        x = np.linalg.cholesky(g @ g.T + np.eye(shape[0]))
    else:
        raise ValueError(f"unknown fill {fill!r}")
    return np.ascontiguousarray(x, dtype=dtype)


def context(config: dict, spans: Optional[Spans]):
    """A ``BlasxContext`` as the configuration states it, its step groups
    timed by the benchmark's spans when ``spans`` is given."""
    from repro.api import BlasxContext
    from repro.core.runtime import RuntimeConfig

    ctx = BlasxContext(RuntimeConfig(**config["runtime"]),
                       tile=config["tile"], dtype=config["dtype"])
    if spans is not None:
        instrument(ctx, spans)
    return ctx
