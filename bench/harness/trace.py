"""The profiler trace of a window, reduced to what the per-layer metrics
read: device busy time, per-kernel device time, the device time of the
other operations, and the idle gaps with what the host was doing.

A device's operations are the events on the ``XLA Ops`` line of its
``/device:TPU:<n>`` plane; an event's name is its HLO instruction, and an
operation that contains others (a ``while``) spans them. The host's spans
are the benchmark's own ``TraceAnnotation`` names (``client.*``), on the
host planes.

A Pallas kernel is a ``custom-call`` instruction. Today's kernels carry no
``name=``, so a kernel is told by the computation XLA names it after: the
projection is ``alsh_project_pallas``, the exact scan
``wl1_scan_topk_pallas``, and every other Pallas call of the query path is
the gather/rerank/top-k kernel (its calls run inside the loops over id
tiles, as ``closed_call``, or as ``gather_rerank_topk_pallas_blocked`` for
the screen's survivors).
"""

from __future__ import annotations

import dataclasses
import glob
import shutil
import tempfile

import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
PALLAS_MARK = " custom-call("
# kernel family -> names that mark it; a Pallas call that matches none is
# the gather/rerank/top-k kernel
KERNELS = {
    "project": ("alsh_project_pallas", "_project_kernel"),
    "exact_scan": ("wl1_scan_topk_pallas", "_scan_topk_kernel"),
}
OTHER_KERNEL = "gather_rerank"


@dataclasses.dataclass(frozen=True)
class Op:
    name: str  # the HLO instruction
    start_ns: float
    end_ns: float
    self_ns: float = 0.0  # time not spent in the operations it contains

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def short(self) -> str:
        return self.name.split(" = ")[0].lstrip("%")


def family(op: Op) -> str | None:
    """The kernel an operation is, or None for an XLA operation."""
    if PALLAS_MARK not in op.name:
        return None
    head = op.name.split(" = ")[0]
    for fam, marks in KERNELS.items():
        if any(m in head for m in marks):
            return fam
    return OTHER_KERNEL


def with_self_time(ops: list) -> list:
    """Each operation with its own time: its span less the spans of the
    operations directly inside it."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.end_ns))
    inner = [0.0] * len(ops)
    stack: list = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end_ns <= o.start_ns:
            stack.pop()
        if stack:
            inner[stack[-1]] += o.end_ns - o.start_ns
        stack.append(i)
    return [dataclasses.replace(o, self_ns=o.end_ns - o.start_ns - c) for o, c in zip(ops, inner)]


def union_seconds(intervals) -> float:
    """Length of the union of (start_ns, end_ns) intervals, in seconds."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total * 1e-9


def gaps(intervals, start_ns: float, end_ns: float):
    """(start_ns, end_ns) of the stretches of [start, end] no interval covers."""
    out, at = [], start_ns
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, end_ns)))
        at = max(at, e)
        if at >= end_ns:
            break
    if at < end_ns:
        out.append((at, end_ns))
    return [g for g in out if g[1] > g[0]]


@dataclasses.dataclass
class Reduced:
    ops: list  # Op, device 0's operations, cut to the window
    spans: list  # (name, start_ns, end_ns) host spans of the benchmark
    start_ns: float
    end_ns: float

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def busy_s(self) -> float:
        return union_seconds([(o.start_ns, o.end_ns) for o in self.ops])

    def kernel_s(self, fam: str) -> float:
        return sum(o.seconds for o in self.ops if family(o) == fam)

    def xla_ops_s(self) -> float:
        """Device time of the operations that are not Pallas kernels (each
        counted once, without what it contains)."""
        return sum(o.self_ns for o in self.ops if family(o) is None) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        by_name: dict = {}
        for o in self.ops:
            key = family(o) or o.short
            by_name[key] = by_name.get(key, 0.0) + o.self_ns * 1e-9
        device_ops = sorted(by_name.items(), key=lambda x: -x[1])[:top]
        idle: dict = {}
        for s, e in gaps([(o.start_ns, o.end_ns) for o in self.ops], self.start_ns, self.end_ns):
            label = self.host_doing((s + e) / 2)
            idle[label] = idle.get(label, 0.0) + (e - s) * 1e-9
        idle_gaps = sorted(idle.items(), key=lambda x: -x[1])[:top]
        return {"device_ops": [list(x) for x in device_ops],
                "idle_gaps": [list(x) for x in idle_gaps]}

    def host_doing(self, t_ns: float) -> str:
        """The innermost benchmark span that covers ``t_ns``."""
        best = None
        for name, s, e in self.spans:
            if s <= t_ns <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "outside client spans"


def reduce_profile(profile) -> Reduced:
    """A ``jax.profiler.ProfileData`` to the first chip's operations and the
    host spans. The window is the host span ``bench.window``; where it
    holds no device operation (or is missing), the stretch from the first
    to the last device operation."""
    ops, spans = [], []
    chips = sorted((int(m.group(1)), p) for p in profile.planes
                   if (m := DEVICE_PLANE.match(p.name)))
    if chips:
        for line in chips[0][1].lines:
            if line.name == OPS_LINE:
                ops += [Op(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN or ev.name.startswith("client."):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    start = end = 0.0
    if window:
        start, end = window[0][1], window[0][2]
    if ops and not any(o.end_ns > start and o.start_ns < end for o in ops):
        start, end = min(o.start_ns for o in ops), max(o.end_ns for o in ops)
    inside = [dataclasses.replace(o, start_ns=max(o.start_ns, start), end_ns=min(o.end_ns, end))
              for o in ops if o.end_ns > start and o.start_ns < end]
    return Reduced(with_self_time(inside), [s for s in spans if s[0] != WINDOW_SPAN], start,
                   end)


class Capture:
    """Profile the ``with`` block when on; ``reduce()`` reads the trace
    back and deletes it."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def __enter__(self):
        if self.on:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
            self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._span.__enter__()
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax

            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return False

    def reduce(self) -> Reduced:
        import jax

        try:
            path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)[0]
            return reduce_profile(jax.profiler.ProfileData.from_file(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span of the benchmark's client, in the trace when one runs."""
    import jax

    return jax.profiler.TraceAnnotation(f"client.{name}")
