"""Attribute device time to the engine's graph nodes, and split the idle
gaps of a closed loop by cause, from one JAX profiler trace.

This reads what the program itself puts in the trace.

* Every device op of the fused program carries its graph node's name as a
  component of its ``op_name``: ``FusedEngine._chain`` runs each node
  under ``jax.named_scope``.  A TPU trace names each op event by its HLO
  instruction (``%mvu_int.24 = s32[...] custom-call(...)``) and carries
  no op name, so ``hlo_ops`` (``hlo_op_names`` of the compiled program's
  text) maps the instruction to its ``op_name``, and the node is the
  first of its ``/``-separated parts that names a node.  Busy time that
  no node's op covers is *plumbing*: the ops ``_stream`` adds around the
  nodes (pad, microbatch slicing, output stacking) and the gaps inside
  the ``lax.map`` loop, which the ``while`` event covers.
* With a ``repro.telemetry.Tracer`` handed to ``FusedEngine.dispatch``,
  each call of a closed loop leaves an ``engine.dispatch`` span on the
  host plane, then a ``block`` span while the client copies the result to
  the host.  Its run of the program on the device (``XLA Modules`` line)
  is the one whose completion the TPU runtime reports in between: the
  host's ``CompleteCallbacks`` event carries the run's ``run_id``.
  The profiler aligns the device's clock to the host's only roughly: on a
  TPU v5e host, two traces in one process placed the device's ops 1.3 ms
  apart against the host's spans.  So each part of the idle gap before a
  run, from the last op of the previous run to the first op of this one
  (device clock), is a difference taken on one clock:

  - *host*: from the end of the previous ``block`` to the end of this
    call's ``engine.dispatch``, the host in Python (host clock);
  - *fetch*: from the runtime's completion callback of the previous run
    to the end of its ``block``, while its result comes back (host clock);
  - *launch*: the rest, the program enqueued but not started, together
    with the time the runtime took to notice the previous run's end.
"""

from __future__ import annotations

import dataclasses
import re

from bench.harness import trace

ENGINE_DISPATCH = "engine.dispatch"
BLOCK = "block"
CALLBACK = "CompleteCallbacks"  # the runtime's host thread, once a run is done
MODULES_LINE = "XLA Modules"
_INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w\-.]*?)(?:\s*=.*)?$", re.S)
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w\-.]+) = .*?op_name="([^"]*)"',
                     re.M)


@dataclasses.dataclass
class Gap:
    """The idle gap before one run of a closed loop, split by cause."""

    launch_s: float
    fetch_s: float  # of the previous call's result
    host_s: float


@dataclasses.dataclass
class NodeSummary:
    window_s: float
    busy_s: float  # union of op intervals in the window, loops included
    node_s: dict  # node -> seconds its ops ran in the window
    plumbing_s: float  # busy seconds in no node's op
    unscoped: dict  # op base name -> events in no node's scope (not loops)
    runs: int  # runs of the program wholly inside the window
    run_node_s: dict  # node -> seconds over those runs
    dispatch_s: list  # ``engine.dispatch`` spans that start in the window
    gaps: list  # Gap before each matched call's run, after the first

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    def plumbing_share(self) -> float | None:
        return 100.0 * self.plumbing_s / self.busy_s if self.busy_s else None

    def bottleneck(self, batch: int) -> tuple[str, float] | None:
        """The node with the most device time over whole runs, and its
        device microseconds per sample."""
        per_sample = self.us_per_sample(batch)
        if not per_sample:
            return None
        return max(per_sample.items(), key=lambda kv: kv[1])

    def dispatch_ms(self) -> float | None:
        if not self.dispatch_s:
            return None
        return 1e3 * sum(self.dispatch_s) / len(self.dispatch_s)

    def gap_ms(self, cause: str) -> float | None:
        """Mean ``launch``, ``fetch`` or ``host`` milliseconds per call."""
        if not self.gaps:
            return None
        total = sum(getattr(g, cause + "_s") for g in self.gaps)
        return 1e3 * total / len(self.gaps)

    def device_nodes(self, top: int = 10) -> list:
        ranked = sorted(self.node_s.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v] for k, v in ranked]

    def us_per_sample(self, batch: int) -> dict:
        """Each node's device microseconds per sample, over whole runs."""
        if not self.runs:
            return {}
        return {k: 1e6 * v / (self.runs * batch)
                for k, v in self.run_node_s.items()}


def instruction(name: str) -> str:
    """``%mvu_int.24 = s32[...] custom-call(...)`` -> ``mvu_int.24``."""
    m = _INSTRUCTION.match(name)
    return m.group(1) if m else name


def hlo_op_names(compiled_text: str) -> dict:
    """HLO instruction name -> ``op_name`` in a compiled program's text."""
    return dict(_HLO_OP.findall(compiled_text))


def node_of(op_name: str, nodes) -> str | None:
    """The first of ``op_name``'s ``/``-separated parts that names a node."""
    return next((part for part in op_name.split("/") if part in nodes), None)


def _host_spans(profile, names) -> tuple[dict, dict]:
    """``names`` -> sorted (start, end) spans, and the start of each
    ``CompleteCallbacks`` event by ``run_id``."""
    spans: dict = {name: [] for name in names}
    done: dict = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    spans[ev.name].append((ev.start_ns, ev.end_ns))
                elif ev.name == CALLBACK:
                    run_id = dict(ev.stats).get("run_id")
                    if run_id is not None:
                        done[str(run_id)] = ev.start_ns
    return {k: sorted(v) for k, v in spans.items()}, done


def _measure(intervals) -> float:
    return sum(b - a for a, b in trace._union(intervals))


def _device_plane(profile):
    for plane in profile.planes:
        if trace._DEVICE.match(plane.name):
            return plane
    raise ValueError("no /device:TPU:<n> plane in the trace")


def _gaps(dispatches, runs, blocks, done) -> list:
    """The gap before each matched call's run, after the first.  ``runs``
    holds each run's (first op start, last op end, run_id) in order;
    ``done`` the host time its completion was reported, by run_id."""
    runs = [run for run in runs if run[2] in done]
    gaps, prev = [], None
    r = b = 0
    for d0, d1 in dispatches:
        while b < len(blocks) and blocks[b][0] < d1:
            b += 1
        while r < len(runs) and done[runs[r][2]] <= d0:
            r += 1
        if b == len(blocks) or r == len(runs):
            break
        b1 = blocks[b][1]
        if done[runs[r][2]] > b1:  # no run reported done within this call
            prev = None
            continue
        if prev is not None:
            (_, last, run_id), prev_b1 = prev
            gap = runs[r][0] - last  # device clock
            host = d1 - prev_b1  # host clock, as is fetch
            fetch = prev_b1 - done[run_id]
            gaps.append(Gap((gap - host - fetch) * 1e-9, fetch * 1e-9,
                            host * 1e-9))
        prev = (runs[r], b1)
        r += 1
        b += 1
    return gaps


def _ops(plane, nodes, hlo_ops) -> tuple[list, list]:
    """(start, end, base name, node or None) per op event, in start order,
    and the (start, end, run_id) of each run of a program."""
    ops, modules = [], []
    for line in plane.lines:
        if line.name == trace.OPS_LINE:
            for ev in line.events:
                op_name = hlo_ops.get(instruction(ev.name), "")
                ops.append((ev.start_ns, ev.end_ns, trace.base_name(ev.name),
                            node_of(op_name, nodes)))
        elif line.name == MODULES_LINE:
            modules.extend((ev.start_ns, ev.end_ns,
                            str(dict(ev.stats).get("run_id")))
                           for ev in line.events)
    return sorted(ops, key=lambda op: op[:2]), sorted(modules)


def reduce(profile, *, nodes, hlo_ops: dict) -> NodeSummary:
    """Summarize a ``jax.profiler.ProfileData`` of one device running the
    engine inside a ``bench.window`` span.  ``nodes`` names the graph's
    nodes; ``hlo_ops`` is ``hlo_op_names`` of the engine's compiled
    program."""
    host, done = _host_spans(profile, (trace.WINDOW, ENGINE_DISPATCH, BLOCK))
    if not host[trace.WINDOW]:
        raise ValueError(f"no {trace.WINDOW!r} span in the trace")
    w0, w1 = host[trace.WINDOW][0]
    ops, modules = _ops(_device_plane(profile), frozenset(nodes), hlo_ops)

    busy, scoped, by_node, unscoped = [], [], {}, {}
    for a, b, name, node in ops:
        a, b = max(a, w0), min(b, w1)
        if b <= a:
            continue
        busy.append((a, b))
        if node is not None:
            scoped.append((a, b))
            by_node.setdefault(node, []).append((a, b))
        elif name not in trace.CONTAINERS:
            unscoped[name] = unscoped.get(name, 0) + 1

    runs, run_node_s, n_full = [], {}, 0
    i = 0
    for m0, m1, run_id in modules:
        while i < len(ops) and ops[i][0] < m0:
            i += 1
        j = i
        while j < len(ops) and ops[j][0] < m1:
            j += 1
        if j == i:
            continue
        run = (ops[i][0], max(op[1] for op in ops[i:j]), run_id)
        runs.append(run)
        if w0 <= run[0] and run[1] <= w1:
            n_full += 1
            for a, b, _, node in ops[i:j]:
                if node is not None:
                    run_node_s[node] = run_node_s.get(node, 0.0) + (b - a) * 1e-9
        i = j

    dispatches = [(a, b) for a, b in host[ENGINE_DISPATCH] if w0 <= a < w1]
    busy_ns = _measure(busy)
    return NodeSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9,
        node_s={k: _measure(v) * 1e-9 for k, v in by_node.items()},
        plumbing_s=(busy_ns - _measure(scoped)) * 1e-9, unscoped=unscoped,
        runs=n_full, run_node_s=run_node_s,
        dispatch_s=[(b - a) * 1e-9 for a, b in dispatches],
        gaps=_gaps(dispatches, runs, host[BLOCK], done))
