"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the chip, named by its HLO instruction, Pallas
kernels under their kernel name (``%conv_mvu_xnor.36 = s32[...]
custom-call(...)``).  A ``while`` loop is an event too, around the ops of
its body: it counts as busy time, not as an op of the breakdown.  The benchmark's own host
spans (``jax.profiler.TraceAnnotation``) are on the host planes, on the
same clock: ``bench.window`` bounds the traced window, and the innermost
span around each idle gap says what the host was doing in it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
# control flow that encloses other ops on the same line: busy, but not an
# operation of its own in the breakdown
CONTAINERS = frozenset({"while", "conditional", "call"})
_DEVICE = re.compile(r"^/device:TPU:\d+$")
# an op event is named by its HLO instruction, often the whole line:
# "%conv_mvu_xnor.36 = s32[1,960,64]{...} custom-call(...), ..."
_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s*=.*)?$", re.S)


def base_name(name: str) -> str:
    """``%conv_mvu_xnor.3 = s32[...] custom-call(...)`` -> ``conv_mvu_xnor``."""
    m = _NAME.match(name)
    return m.group(1) if m else name


@dataclasses.dataclass
class TraceSummary:
    chips: int  # device planes read
    window_s: float  # length of the traced window
    busy_s: float  # union of op intervals, averaged over chips
    op_s: dict  # base op name -> seconds, summed over chips
    op_count: dict  # base op name -> events, summed over chips
    idle_by_host: dict  # host span name -> idle seconds, averaged over chips
    longest_gaps: list  # the longest idle gaps, (host span name, seconds)

    def kernel(self, name: str) -> tuple[float, int]:
        """(seconds, calls) of one kernel over every chip."""
        return self.op_s.get(name, 0.0), self.op_count.get(name, 0)

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v / self.chips] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.longest_gaps]}


def idle_share(ctx) -> float | None:
    """Percent of the traced window in which no operation ran on the
    device, averaged over the chips the cell uses."""
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _innermost(spans: list) -> tuple[list, list]:
    """Properly nested host spans flattened into disjoint segments, each
    labelled with its innermost span: ``(starts, [(end, name), ...])``."""
    starts, segs = [], []
    stack: list = []
    pos = None

    def emit(upto):
        nonlocal pos
        if stack and pos is not None and upto > pos:
            starts.append(pos)
            segs.append((upto, stack[-1][1]))
        pos = upto

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            emit(stack[-1][0])
            stack.pop()
        emit(a)
        stack.append((b, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return starts, segs


def _label(starts: list, segs: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < segs[i][0]:
        return segs[i][1]
    return "host_other"


def reduce(profile, *, host_spans: frozenset) -> TraceSummary:
    """Summarize a ``jax.profiler.ProfileData``.  ``host_spans`` names the
    benchmark's host annotations that may label an idle gap."""
    window = None
    spans = []
    device_planes = []
    for plane in profile.planes:
        if _DEVICE.match(plane.name):
            device_planes.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in host_spans:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    if not device_planes:
        raise ValueError("no /device:TPU:<n> plane in the trace")
    w0, w1 = window
    starts, segs = _innermost(spans)
    op_s: dict = {}
    op_count: dict = {}
    busy = 0.0
    idle: dict = {}
    gaps: list = []
    for plane in device_planes:
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a, b = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if b <= a:
                    continue
                ivs.append((a, b))
                name = base_name(ev.name)
                if name in CONTAINERS:
                    continue
                op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9
                op_count[name] = op_count.get(name, 0) + 1
        merged = _union(ivs)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                who = _label(starts, segs, (a + b) / 2)
                idle[who] = idle.get(who, 0.0) + (b - a) * 1e-9
                gaps.append(((b - a) * 1e-9, who))
    n = len(device_planes)
    return TraceSummary(chips=n, window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
                        op_s=op_s, op_count=op_count,
                        idle_by_host={k: v / n for k, v in idle.items()},
                        longest_gaps=[(who, d) for d, who in
                                      sorted(gaps, reverse=True)[:10]])


def load(log_dir: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(log_dir))
