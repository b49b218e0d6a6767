"""Reduction of a JAX profiler trace to device busy time, kernel time,
collective time and idle gaps.

A trace is reduced to plain records first (``Trace``): the operations each
device ran (plane ``/device:TPU:<n>``, line ``XLA Ops``) and the spans the
benchmark opened on the host (``bench.*``). Everything after that works on
those records, so the tests can hand-build a trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
# collective operations as XLA:TPU names them in the trace: the async pairs
# (start/done) and the synchronous forms
COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all)"
    r"(-start|-done)?(\.\d+)?(\s|$)")
CONTAINER = re.compile(r"\s(while|conditional|call)\(")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str           # XLA:TPU names an op by its HLO instruction's text
    start: float        # seconds on the trace's clock
    end: float

    @property
    def short(self) -> str:
        """``%fusion.12 bf16[4,1024,3072]``: the instruction and its first
        result type, without operands."""
        head, eq, rest = self.name.partition(" = ")
        if not eq:
            return self.name[:120]
        return f"{head} {rest.split(' ')[0].split('{')[0].lstrip('(')}"[:120]

    @property
    def container(self) -> bool:
        """A while, conditional or call op, whose time its body's ops
        already show."""
        return bool(CONTAINER.search(self.name))


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]                  # device id -> ops, by start
    spans: List[Tuple[str, float, float]]     # host spans opened by bench

    def window(self) -> Tuple[float, float]:
        wins = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        return wins[-1]


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops: Dict[int, List[Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                out = ops.setdefault(dev, [])
                for ev in line.events:
                    out.append(Op(ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    for v in ops.values():
        v.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s[1])
    return Trace(ops, spans)


def _clip(ops: Sequence[Op], w0: float, w1: float):
    for o in ops:
        s, e = max(o.start, w0), min(o.end, w1)
        if e > s:
            yield o, s, e


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace, device: int) -> float:
    """Seconds of the window in which some operation ran on ``device``."""
    w0, w1 = trace.window()
    merged = union([(s, e) for _, s, e in _clip(trace.ops.get(device, ()),
                                                 w0, w1)])
    return sum(e - s for s, e in merged)


def mean_busy_s(trace: Trace) -> float:
    devs = sorted(trace.ops)
    return sum(busy_s(trace, d) for d in devs) / len(devs) if devs else 0.0


def op_time_s(trace: Trace, device: int, pred: Callable[[Op], bool]) -> float:
    """Summed device time, inside the window, of the ops ``pred`` selects."""
    w0, w1 = trace.window()
    return sum(e - s for o, s, e in _clip(trace.ops.get(device, ()), w0, w1)
               if pred(o))


def mean_op_time_s(trace: Trace, pred: Callable[[Op], bool]) -> float:
    devs = sorted(trace.ops)
    if not devs:
        return 0.0
    return sum(op_time_s(trace, d, pred) for d in devs) / len(devs)


def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE.match(op.name.lstrip("%")))


def idle_gaps(trace: Trace, device: int) -> List[Tuple[float, float]]:
    """Intervals of the window in which ``device`` ran nothing."""
    w0, w1 = trace.window()
    merged = union([(s, e) for _, s, e in _clip(trace.ops.get(device, ()),
                                                 w0, w1)])
    gaps, t = [], w0
    for s, e in merged:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def host_activity(trace: Trace, t0: float, t1: float) -> str:
    """The benchmark's host span that overlaps [t0, t1] most, without the
    ``bench.`` prefix; ``host`` where none does."""
    best, name = 0.0, "host"
    for n, s, e in trace.spans:
        if n == WINDOW_SPAN:
            continue
        ov = min(e, t1) - max(s, t0)
        if ov > best:
            best, name = ov, n[len("bench."):]
    return name


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` ops with the most device time in the window, seconds
    averaged over devices, by ``Op.short``; while loops and other
    containers are left out (their bodies' ops are counted). Numbered
    instances of one op (``fusion.12``) are kept apart: each is one piece
    of the compiled program."""
    devs = sorted(trace.ops)
    if not devs:
        return []
    w0, w1 = trace.window()
    tot: Dict[str, float] = {}
    for d in devs:
        for o, s, e in _clip(trace.ops[d], w0, w1):
            if not o.container:
                tot[o.short] = tot.get(o.short, 0.0) + (e - s) / len(devs)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def top_gaps(trace: Trace, k: int = 10,
             device: Optional[int] = None) -> List[List]:
    """The ``k`` longest idle gaps of one device, each named by the host
    span it falls in: ``[["input@12.345678", seconds], ...]`` where the
    number is the gap's start in seconds from the window's start."""
    devs = sorted(trace.ops)
    if not devs:
        return []
    dev = devs[0] if device is None else device
    w0, _ = trace.window()
    gaps = sorted(idle_gaps(trace, dev), key=lambda g: g[0] - g[1])[:k]
    return [[f"{host_activity(trace, s, e)}@{s - w0:.6f}", e - s]
            for s, e in gaps]
