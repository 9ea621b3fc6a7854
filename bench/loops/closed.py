"""``loop: closed`` — one caller runs a mix's ``calls`` back to back on one
``BlasxContext``.

A mix (``bench/traffic/<mix>.json``) names its operands (shape and fill),
how the caller holds them (``hold: handles`` tiles them once and reuses
the handles every call; ``hold: arrays`` passes the raw host arrays every
call), how many seeded operand sets it cycles through (``sets``), the
calls of one iteration (routine, operand and keyword arguments, the name
of the output), and which outputs are compared (``compare``).

The window starts iterations until its seconds have passed and lets the
last run to its end; ``tflops`` is the work of every iteration started
over the time from the first start to the last end.  Every iteration's
compared outputs are kept on rows drawn from the seed, a few of every
tile-row block, and compared with the float64 reference once the window
has closed.

A loop module holds ``Loop(cell, seed, spans)`` with ``contexts``,
``warm()``, ``window(seconds) -> Outcome``, ``close()``,
``numbers(outcome)`` and ``control_numbers(outcome, mm)``.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from bench import check
from bench.cell import Cell, routine
from bench.generator import (ROWS_PER_BLOCK, check_keys, config_sizes,
                             context, make, rng)
from bench.spans import Spans
from bench.window import Call, Outcome, rate

MIX_KEYS = ("why", "loop", "hold", "sets", "operands", "calls", "compare")
CALL_KEYS = ("routine", "args", "kwargs", "operand_kwargs", "out")
# stream tags of np.random.default_rng([seed, tag, ...])
_OPERANDS, _ROWS = range(2)


def _call_args(call: dict, env: dict):
    args = [env[a] for a in call["args"]]
    kw = dict(call.get("kwargs", {}))
    kw.update({k: env[v] for k, v in call.get("operand_kwargs", {}).items()})
    return args, kw


class Loop:

    def __init__(self, cell: Cell, seed: int, spans: Optional[Spans] = None):
        cfg, tr = cell.config, cell.traffic
        check_keys(tr, MIX_KEYS, f"traffic of {cell.name}")
        for call in tr["calls"]:
            check_keys(call, CALL_KEYS, f"a call of {cell.name}")
        self.calls_spec = tr["calls"]
        self.compare = tr["compare"]
        self.spans = spans or Spans(annotate=False)
        env = config_sizes(cfg)
        dtype = np.dtype(cfg["dtype"])
        self.sets = [{name: make(spec, env, rng(seed, _OPERANDS, s, i), dtype)
                      for i, (name, spec) in enumerate(tr["operands"].items())}
                     for s in range(tr.get("sets", 1))]
        self.ctx = context(cfg, spans)
        self.contexts = [self.ctx]
        if tr["hold"] == "handles":
            self.inputs = [{k: self.ctx.tile(v) for k, v in ops.items()}
                           for ops in self.sets]
        elif tr["hold"] == "arrays":
            self.inputs = self.sets
        else:
            raise ValueError(f"unknown hold {tr['hold']!r}")
        # work per iteration and the shape of every output, from shapes
        shapes = {k: tuple(v.shape) for k, v in self.sets[0].items()}
        self.flops = 0
        for call in self.calls_spec:
            mod = routine(call["routine"])
            a, kw = _call_args(call, shapes)
            self.flops += mod.flops(a, kw)
            shapes[call["out"]] = tuple(mod.out_shape(a, kw))
        self.rows = {out: check.sample_rows(shapes[out][0], cfg["tile"],
                                            ROWS_PER_BLOCK,
                                            rng(seed, _ROWS, i))
                     for i, out in enumerate(self.compare)}

    def iterate(self, s: int) -> dict:
        env = dict(self.inputs[s])
        for call in self.calls_spec:
            args, kw = _call_args(call, env)
            with self.spans.call(call["routine"]):
                env[call["out"]] = getattr(self.ctx, call["routine"])(*args, **kw)
        return env

    def warm(self) -> None:
        """Every set has the same shapes: one iteration warms them all."""
        self.iterate(0)

    def window(self, seconds: float) -> Outcome:
        """Start iterations until ``seconds`` have passed; the last runs
        to its end.  The answers are, per iteration, its operand set and
        the sampled rows of each compared output."""
        calls: List[Call] = []
        samples = []
        t_close = time.perf_counter() + seconds
        i = 0
        while True:
            t0 = time.perf_counter()
            if calls and t0 >= t_close:
                break
            s = i % len(self.sets)
            env = self.iterate(s)
            calls.append(Call(t0, time.perf_counter(), self.flops))
            samples.append((s, {o: np.array(env[o].array()[self.rows[o]])
                                for o in self.compare}))
            i += 1
        win = max(c.end for c in calls) - min(c.start for c in calls)
        return Outcome(attempted=len(calls), failed=0, window_s=win,
                       metrics={"tflops": rate(calls) / 1e12},
                       answers=samples)

    def close(self) -> None:
        self.ctx.close()

    def expected(self, s: int, mm=np.matmul, dtype=np.float64) -> dict:
        """The reference's compared rows for operand set ``s``: inputs in
        ``dtype``, every product through ``mm``."""
        env = {k: v.astype(dtype) for k, v in self.sets[s].items()}
        used_later = set()
        for call in self.calls_spec:
            used_later.update(call["args"])
            used_later.update(call.get("operand_kwargs", {}).values())
        out = {}
        for call in self.calls_spec:
            mod = routine(call["routine"])
            args, kw = _call_args(call, env)
            name = call["out"]
            if name in used_later:
                env[name] = mod.reference(args, kw, None, mm)
                if name in self.compare:
                    out[name] = env[name][self.rows[name]]
            elif name in self.compare:
                out[name] = mod.reference(args, kw, self.rows[name], mm)
        return out

    def _worst(self, samples) -> dict:
        refs = {s: self.expected(s) for s in {s for s, _ in samples}}
        return {f"err_{o}": max(check.normwise(got[o], refs[s][o])
                                for s, got in samples)
                for o in self.compare}

    def numbers(self, out: Outcome) -> dict:
        """Worst normwise error of each compared output over every
        iteration of the window, against the float64 reference."""
        return self._worst(out.answers)

    def control_numbers(self, out: Outcome, mm) -> dict:
        """The same numbers with the reference through ``mm``, in float32,
        put in the program's place for every set the window used."""
        used = sorted({s for s, _ in out.answers})
        return self._worst([(s, self.expected(s, mm, np.float32))
                            for s in used])
