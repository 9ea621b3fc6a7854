"""Reduction of a profiler trace to device metrics.

The traced run writes the benchmark's spans (``bench.*``) and the
device's operations into one ``.xplane.pb``, on one clock.  From it:

* the window: the host span ``bench.window``;
* busy time: the union of the intervals of the operations on each
  device plane's ``XLA Ops`` line inside the window, averaged over the
  devices; the idle share is 1 - busy / window;
* the matmul roofline: for every matmul kernel in the window (the
  Pallas ``tpu_custom_call`` matmul and XLA ``dot``), the least time the
  chip could take, the larger of flops / peak and operand-plus-result
  bytes / HBM bandwidth, both counted from the shapes in the kernel's
  HLO text; summed, over the kernels' summed device time;
* the breakdown: the device operations that took most time, and the
  longest idle gaps, each named by the ``bench.*`` spans the host was in.
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .spans import WINDOW_SPAN

OPS_LINE = "XLA Ops"
TOP = 10
_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
          "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 0.5, "u4": 0.5}
_SHAPE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_OP = re.compile(r"\s([a-z][\w-]*)\(")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float  # ns
    end: float    # ns


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CUSTOM" not in plane


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> List[Event]:
    """Every event of the device planes' op lines and of the host's
    ``bench.*`` spans."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = is_device(plane.name)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if device or e.name.startswith("bench."):
                    out.append(Event(plane.name, line.name, e.name,
                                     e.start_ns, e.end_ns))
    return out


def window_of(events: Iterable[Event]) -> Tuple[float, float]:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return min(e.start for e in spans), max(e.end for e in spans)


def merge(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Union of intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def device_ops(events: Iterable[Event]) -> Dict[str, List[Event]]:
    planes: Dict[str, List[Event]] = {}
    for e in events:
        if is_device(e.plane) and e.line == OPS_LINE:
            planes.setdefault(e.plane, []).append(e)
    return planes


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Busy time of the devices inside [lo, hi], averaged over them."""
    planes = device_ops(events)
    if not planes:
        return 0.0
    return sum(sum(b - a for a, b in merge(((e.start, e.end) for e in ops),
                                           lo, hi))
               for ops in planes.values()) / len(planes)


def _shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


def _nbytes(shapes) -> float:
    return sum(_BYTES.get(t, 4) * math.prod(dims) for t, dims in shapes)


def hlo_parts(name: str):
    """``(instruction, op, output shape text, operand text, attributes)``
    of one HLO instruction's text, or None."""
    if not name.startswith("%") or " = " not in name:
        return None
    inst, rhs = name[1:].split(" = ", 1)
    m = _OP.search(rhs)
    if m is None:
        return None
    depth, i = 0, m.end() - 1
    for i in range(m.end() - 1, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[i], 0)
        if depth == 0:
            break
    return inst, m.group(1), rhs[:m.start()], rhs[m.end():i], rhs[i + 1:]


def matmul_cost(name: str) -> Optional[Tuple[float, float]]:
    """``(flops, bytes)`` of a matmul kernel from its HLO text: a Pallas
    matmul ``tpu_custom_call`` (``[..., m, k] @ [..., k, n]``) or an XLA
    ``dot``; None for any other operation."""
    parts = hlo_parts(name)
    if parts is None:
        return None
    inst, op, out_text, args, attrs = parts
    out = _shapes(out_text)
    ins = _shapes(args)
    if not out or len(ins) < 2:
        return None
    if op == "custom-call" and "tpu_custom_call" in attrs \
            and "matmul" in inst:
        k = ins[0][1][-1]
    elif op == "dot":
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", attrs)
        if m is None:
            return None
        k = math.prod(ins[0][1][int(d)] for d in m.group(1).split(",") if d)
    else:
        return None
    flops = 2.0 * math.prod(out[0][1]) * k
    return flops, _nbytes(out[:1]) + _nbytes(ins)


def roofline(events: Sequence[Event], lo: float, hi: float,
             peak_flops: float, hbm_bw: float) -> Optional[dict]:
    """Share (%) of the matmul kernels' device time that their roofline
    needs, and which bound applies; None without a matmul kernel."""
    least_c = least_m = least = spent = 0.0
    for e in events:
        if not (is_device(e.plane) and e.line == OPS_LINE
                and lo <= e.start < hi):
            continue
        cost = matmul_cost(e.name)
        if cost is None:
            continue
        c, m = cost[0] / peak_flops, cost[1] / hbm_bw
        least_c += c
        least_m += m
        least += max(c, m)
        spent += (e.end - e.start) * 1e-9
    if spent <= 0:
        return None
    return {"share": 100.0 * least / spent,
            "bound": "compute" if least_c >= least_m else "memory",
            "kernel_s": spent}


def short_name(name: str) -> str:
    parts = hlo_parts(name)
    if parts is None:
        return name[:80]
    inst, op, out_text, _, _ = parts
    out = _SHAPE.search(out_text)
    return f"{re.sub(r'[.][0-9]+$', '', inst)} {op} " \
           f"{out.group(0) if out else ''}".strip()


def _host_spans(events: Sequence[Event]) -> List[Event]:
    return [e for e in events if not is_device(e.plane)
            and e.name.startswith("bench.") and e.name != WINDOW_SPAN]


def _doing(spans: Sequence[Event], t: float) -> str:
    """The innermost ``bench.*`` span of each host thread at ``t``."""
    inner: Dict[Tuple[str, str], Event] = {}
    for e in spans:
        if e.start <= t < e.end:
            key = (e.plane, e.line)
            if key not in inner or e.end - e.start < \
                    inner[key].end - inner[key].start:
                inner[key] = e
    names = sorted({e.name for e in inner.values()})
    return "+".join(names) if names else "between calls"


def breakdown(events: Sequence[Event], lo: float, hi: float) -> dict:
    totals: Dict[str, float] = {}
    for ops in device_ops(events).values():
        for e in ops:
            if lo <= e.start < hi:
                k = short_name(e.name)
                totals[k] = totals.get(k, 0.0) + (e.end - e.start) * 1e-9
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    planes = device_ops(events)
    gaps: List[Tuple[str, float]] = []
    if planes:
        first = sorted(planes)[0]
        busy = merge(((e.start, e.end) for e in planes[first]), lo, hi)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        spans = _host_spans(events)
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_doing(spans, (a + b) / 2), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in gaps[:TOP]]}


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    roofline: Optional[dict]
    breakdown: dict

    @property
    def idle_share(self) -> Optional[float]:
        """None when the trace holds no device plane to read."""
        if not self.devices:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def summarize(events: Sequence[Event], peaks: dict) -> Summary:
    lo, hi = window_of(events)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns(events, lo, hi) * 1e-9,
        devices=len(device_ops(events)),
        roofline=roofline(events, lo, hi, peaks["bf16_flops_per_s"],
                          peaks["hbm_bytes_per_s"]),
        breakdown=breakdown(events, lo, hi))
