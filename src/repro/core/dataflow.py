"""Streaming-dataflow schedule + executor (FINN backend analog).

FINN connects one compute unit per layer with AXI streams; throughput is set
by the slowest stage and small FIFOs decouple producer/consumer bursts
(paper section 5.3).  TPUs are statically scheduled, so the runtime analog
is (a) this schedule -- per-stage cycle counts, bottleneck stage, FIFO
depths -- and (b) the pipeline-parallel executor in
``repro.distributed.pipeline`` which streams microbatches through stages
with ``ppermute`` transfers standing in for the AXI streams.

``execute`` runs the lowered graph functionally (the behavioural model the
RTL was validated against); integer semantics end-to-end.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import ir, swu as swu_mod
from repro.core.ir import Graph
from repro.core.mvu import MVUConfig, MVULayer
from repro.core.resource_model import MVUResources
from repro.kernels import ops, packing


@dataclasses.dataclass
class StageInfo:
    name: str
    cycles: int
    resources: MVUResources
    fifo_depth: int
    n_pixels: int = 1  # output pixels per sample (conv stages; 1 for dense)
    block_m: int = 128  # resident M tile of the stage's kernel
    branch: str = "main"  # which arm of a fork the stage sits on


@dataclasses.dataclass
class JoinInfo:
    """One fan-in point (elementwise-binary node) of a branched graph.

    FINN sizes the FIFO on the *shorter* arm of a residual join to absorb
    the latency skew between the two branches -- otherwise the early arm
    stalls the whole pipeline while the long arm drains.  ``fifo_depth`` is
    that balance depth in steady-state bursts: the branch latency
    difference divided by the pipeline's initiation interval (how many
    extra results the fast arm produces before the slow arm's first one
    lands), floored at the usual decoupling minimum of 2."""

    name: str
    branches: tuple[str, str]  # branch label of each joined input
    branch_latency: tuple[int, int]  # critical-path cycles into each input
    fifo_depth: int


@dataclasses.dataclass
class DataflowSchedule:
    stages: list[StageInfo]
    joins: list[JoinInfo] = dataclasses.field(default_factory=list)
    # critical-path latency through the DAG (equals the stage sum on
    # chains); None -> fall back to the chain-era sum
    critical_path_cycles: int | None = None

    @property
    def bottleneck(self) -> StageInfo:
        return max(self.stages, key=lambda s: s.cycles)

    @property
    def steady_state_interval(self) -> int:
        """Cycles between successive inferences once the pipeline is full."""
        return self.bottleneck.cycles

    @property
    def latency_cycles(self) -> int:
        if self.critical_path_cycles is not None:
            return self.critical_path_cycles
        return sum(s.cycles for s in self.stages)

    def summary(self) -> dict:
        out = {
            "stages": len(self.stages),
            "latency_cycles": self.latency_cycles,
            "interval_cycles": self.steady_state_interval,
            "bottleneck": self.bottleneck.name,
            "total_bram_bytes": sum(s.resources.bram_bytes for s in self.stages),
            "total_lut_bytes": sum(s.resources.lut_bytes for s in self.stages),
        }
        if self.joins:
            out["joins"] = [{
                "name": j.name, "branches": list(j.branches),
                "branch_latency": list(j.branch_latency),
                "fifo_depth": j.fifo_depth,
            } for j in self.joins]
        return out


# The paper's RTL targets a 200 MHz FPGA clock (section 6); with no measured
# cycle time this nominal clock converts schedule cycles to wall-clock time.
DEFAULT_CLOCK_HZ = 200e6


def interval_seconds(sched: DataflowSchedule, *, cache=None,
                     device: str | None = None,
                     clock_hz: float = DEFAULT_CLOCK_HZ) -> float:
    """Wall-clock seconds per steady-state interval (one microbatch burst).

    This is the bridge from the schedule's cycle algebra to serving-time
    budgets: the continuous batcher flushes when a request's deadline slack
    shrinks to one engine interval (``repro.serving.batcher``).  When an
    autotune cache holds a *measured* cycle time for this device (recorded
    by ``repro.serving.batcher.calibrate_cycle_time`` or a benchmark run),
    that measurement wins; otherwise the nominal ``clock_hz`` converts the
    analytic cycle count.
    """
    from repro.core import autotune

    if cache is None:
        try:
            cache = autotune.default_cache()
        except Exception:  # pragma: no cover - configs unavailable
            cache = None
    if cache is not None:
        ent = cache.get(autotune.cycle_time_key(device))
        if ent is not None and ent.get("s_per_cycle"):
            return sched.steady_state_interval * float(ent["s_per_cycle"])
    return sched.steady_state_interval / clock_hz


def schedule(graph: Graph) -> DataflowSchedule:
    info = ir.io_shapes(graph)
    branches = ir.branch_labels(graph)
    stages: list[StageInfo] = []
    # per-node bookkeeping threaded along edges (the chain era threaded one
    # running value through list order): nearest upstream MVU stage's cycle
    # count, and the critical-path latency into each node's output
    upstream: dict[str, int | None] = {}
    lat: dict[str, int] = {}
    for node, _, out_shape in info:
        ins = node.inputs or ()
        prevs = [upstream.get(s) for s in ins]
        prev_cycles = max((p for p in prevs if p is not None), default=None)
        in_lat = max((lat[s] for s in ins), default=0)
        if node.op not in ("mvu", "conv_mvu"):
            upstream[node.name] = prev_cycles
            lat[node.name] = in_lat
            continue
        cfg: MVUConfig = node.attrs["config"]
        px = ir.n_pixels(out_shape)
        layer = MVULayer(cfg)
        res = layer.resources(n_pixels=px)
        # FIFO sizing: enough to absorb one producer burst while the
        # consumer drains at its own rate (paper 5.3.2's small FIFO).  At a
        # fan-in the slowest producer governs the drain ratio.
        fold = cfg.resolved_folding()
        burst = fold.pe  # outputs produced per cycle group
        drain = 1 if prev_cycles is None else max(1, res.cycles // max(prev_cycles, 1))
        fifo = max(2, burst * min(drain, 8))
        stages.append(StageInfo(node.name, res.cycles, res, fifo,
                                n_pixels=px, block_m=cfg.block_m,
                                branch=branches.get(node.name, "main")))
        upstream[node.name] = res.cycles
        lat[node.name] = in_lat + res.cycles
    # fan-in FIFOs: balance the latency skew between the joined branches
    # (JoinInfo docstring) against the pipeline's steady-state interval
    interval = max((s.cycles for s in stages), default=1)
    joins = [
        JoinInfo(
            node.name,
            tuple(branches.get(s, "main") for s in node.inputs),
            tuple(lat[s] for s in node.inputs),
            max(2, -(-abs(lat[node.inputs[0]] - lat[node.inputs[1]])
                     // max(1, interval))),
        )
        for node, _, _ in info if node.op in ir.JOIN_OPS
    ]
    return DataflowSchedule(stages, joins=joins,
                            critical_path_cycles=max(lat.values(), default=0))


def _int_range(node, ins: list, in_shapes: tuple) -> tuple[int, int] | None:
    """Static integer range of one node's output, given its inputs' ranges
    and shapes (None = not a bounded integer stream)."""
    if node.op == "input":
        bits = node.attrs.get("bits")
        if bits is None:
            return None
        if node.attrs.get("signed") is False:
            return (0, 2**bits - 1)
        # either sign: the feed may be signed or unsigned on ``bits`` bits
        return (1 - 2**bits, 2**bits - 1)
    if node.op == "quant_act":
        return (0, 2 ** node.attrs["bits"] - 1)
    if node.op in ("swu", "maxpool", "flatten"):
        return ins[0]
    if node.op == "global_pool":
        if ins[0] is None:
            return None
        h, w, _ = in_shapes[0]
        return (ins[0][0] * h * w, ins[0][1] * h * w)
    if node.op == "add_threshold":
        return (0, int(node.params["thresholds"].shape[-1]))
    if node.op in ("mvu", "conv_mvu"):
        p = node.params.get("mvu")
        if p is None or p.thresholds is None:
            return None  # raw accumulator or float scale
        return (0, int(p.thresholds.shape[-1]))
    if node.op in ir.ELTWISE_OPS and None not in ins:
        scales = node.attrs.get("scales", (1, 1))
        if not all(isinstance(s, int) for s in scales):
            return None
        (alo, ahi), (blo, bhi) = (sorted((lo * s, hi * s))
                                  for (lo, hi), s in zip(ins, scales))
        if node.op == "add":
            return (alo + blo, ahi + bhi)
        if node.op == "sub":
            return (alo - bhi, ahi - blo)
        prods = (alo * blo, alo * bhi, ahi * blo, ahi * bhi)
        return (min(prods), max(prods))
    return None


def int_ranges(graph) -> dict[str, tuple[int, int] | None]:
    """Every node's static output range, keyed by name (:func:`_int_range`
    carried along the edges in dataflow order)."""
    ranges: dict[str, tuple[int, int] | None] = {}
    for node, in_shapes, _ in ir.io_shapes(graph):
        ranges[node.name] = _int_range(
            node, [ranges.get(s) for s in node.inputs], in_shapes)
    return ranges


# the integer dtypes an MVU's input stream may be narrowed to, narrowest
# first: int8 feeds the MXU in one pass, uint8 in two base-256 digits,
# int16 in three (``kernels._common.int8_digits``)
NARROW_DTYPES = (jnp.int8, jnp.uint8, jnp.int16)


def narrow_inputs(graph) -> dict[str, jnp.dtype]:
    """The mvu/conv_mvu nodes whose input stream provably fits a narrower
    integer dtype, each with the narrowest that holds it.

    Static ranges, each proved from the node's own producer: an input
    node's ``bits`` (unsigned where ``signed`` is False, else either sign),
    a quant_act's grid, a thresholded MVU's or join's levels [0, T], a
    global pool's sum over its map, carried through swu/maxpool/flatten and
    combined by the eltwise ops with integer scales; anything else is
    unknown.  :func:`node_runner` casts these streams, so an int8 stream
    runs the MXU kernels in one pass and a wider one in exactly as many
    base-256 digits as its range needs (``kernels._common.by_int8_digits``).
    """
    ranges = int_ranges(graph)
    out = {}
    for node in ir.as_graph(graph):
        if node.op not in ("mvu", "conv_mvu"):
            continue
        r = ranges.get(node.inputs[0])
        if r is None:
            continue
        dtype = next((d for d in NARROW_DTYPES
                      if jnp.iinfo(d).min <= r[0] and r[1] <= jnp.iinfo(d).max),
                     None)
        if dtype is not None:
            out[node.name] = jnp.dtype(dtype)
    return out


def node_runner(node, *, in_dtype=None):
    """Per-node semantics as ``(params, fn)`` with ``fn(params, *xs) -> x``.

    The eager interpreter (:func:`execute`) and the fused engine
    (``repro.core.engine``) both apply nodes through this single definition,
    so the jit-compiled engine is bit-exact with the behavioural model by
    construction.  ``params`` is the node's traced pytree (or ``None``).
    Single-input ops take one array; join ops take two.  ``in_dtype``
    (from :func:`narrow_inputs`) carries an MVU's input in that dtype,
    which the range proof makes lossless.
    """
    if node.op == "input":
        return None, lambda p, x: x
    if node.op in ir.ELTWISE_OPS:
        sa, sb = node.attrs.get("scales", (1, 1))
        opf = {"add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply}[node.op]

        def run_eltwise(p, a, b):
            # FINN broadcast semantics on per-sample shapes: align trailing
            # dims, keeping the batch dim (axis 0) out of the broadcast by
            # padding singleton dims right after it.
            rank = max(a.ndim, b.ndim)
            a2 = a.reshape(a.shape[0], *((1,) * (rank - a.ndim)), *a.shape[1:])
            b2 = b.reshape(b.shape[0], *((1,) * (rank - b.ndim)), *b.shape[1:])
            # per-input integer quantization-alignment scales
            return opf(a2 * sa, b2 * sb)

        return None, run_eltwise
    if node.op == "add_threshold":
        scales = tuple(node.attrs.get("scales", (1, 1)))
        return node.params["thresholds"], lambda t, a, b: ops.add_threshold(
            a, b, t, scales=scales)
    if node.op == "swu":
        kd, st, pd = node.attrs["kernel"], node.attrs["stride"], node.attrs["pad"]

        def run_swu(p, x):
            # keep the spatial layout so conv stages chain: (B, OH, OW, K)
            b, h, w, _ = x.shape
            cols = swu_mod.sliding_window(x, kd, st, pd)  # (B, P, K)
            oh = swu_mod.out_dim(h, kd, st, pd)
            ow = swu_mod.out_dim(w, kd, st, pd)
            return cols.reshape(b, oh, ow, cols.shape[-1])

        return None, run_swu
    if node.op == "conv_mvu":
        cfg: MVUConfig = node.attrs["config"]
        kd, st, pd = node.attrs["kernel"], node.attrs["stride"], node.attrs["pad"]

        def run_conv(p, x):
            b, h, w, _ = x.shape
            if in_dtype is not None:
                x = x.astype(in_dtype)
            out = ops.conv_mvu(
                x, p.weights,
                kernel=kd, stride=st, pad=pd, mode=cfg.mode,
                k_bits=cfg.in_features if cfg.mode == "xnor" else None,
                thresholds=p.thresholds, out_scale=p.out_scale,
                backend=cfg.backend, **cfg.kernel_blocks(),
            )  # (B, OH*OW, N)
            oh = swu_mod.out_dim(h, kd, st, pd)
            ow = swu_mod.out_dim(w, kd, st, pd)
            return out.reshape(b, oh, ow, cfg.out_features)

        return node.params["mvu"], run_conv
    if node.op == "maxpool":
        size = node.attrs["size"]
        st = node.attrs.get("stride", size)
        pd = node.attrs.get("pad", 0)

        def run_pool(p, x):
            # padded cells hold the dtype's least value: they never win
            init = x.dtype.type(jnp.iinfo(x.dtype).min) if jnp.issubdtype(
                x.dtype, jnp.integer) else x.dtype.type(-jnp.inf)
            return jax.lax.reduce_window(
                x, init, jax.lax.max,
                (1, size, size, 1), (1, st, st, 1),
                ((0, 0), (pd, pd), (pd, pd), (0, 0)),
            )

        return None, run_pool
    if node.op == "global_pool":
        return None, lambda p, x: jnp.sum(x, axis=(1, 2), dtype=jnp.int32)
    if node.op == "flatten":
        return None, lambda p, x: x.reshape(x.shape[0], -1)
    if node.op == "mvu":
        cfg: MVUConfig = node.attrs["config"]
        layer = MVULayer(cfg)

        def run_mvu(p, x):
            if cfg.mode == "xnor" and x.dtype != jnp.uint32:
                x = packing.pack_bits(x.astype(jnp.int32))
            elif in_dtype is not None:
                x = x.astype(in_dtype)
            return layer(p, x)

        return node.params["mvu"], run_mvu
    if node.op == "batchnorm":
        p = {k: node.params[k] for k in ("gamma", "beta", "mean", "var")}
        return p, lambda p, x: (
            (x - p["mean"]) * p["gamma"] / jnp.sqrt(p["var"] + 1e-5) + p["beta"]
        )
    if node.op == "quant_act":
        bits = node.attrs["bits"]
        s = node.attrs.get("act_scale", 1.0)
        # round-half-up: level j iff x >= (j - 0.5) * s, the multi-threshold
        # unit's decision rule, so threshold fusion (streamline /
        # fuse_epilogues) is exact even at half-level ties.
        return None, lambda p, x: jnp.clip(
            jnp.floor(x / s + 0.5), 0, 2**bits - 1
        ).astype(jnp.int32)
    raise ValueError(f"unknown op {node.op!r} ({node.name})")


def trace(graph: Graph, x) -> dict[str, jax.Array]:
    """Run the graph eagerly and return EVERY node's output, keyed by name.

    This is the DAG interpreter's environment: :func:`execute` reads the
    sink out of it, and the build pipeline's divergence localizer compares
    two of them node-by-node to name the branch/node where a rewrite first
    changed the numbers.  ``x`` is one array when the graph has a single
    input node, or a ``{input-name: array}`` dict for multi-input graphs.
    """
    order = ir.toposort(graph)
    if isinstance(x, dict):
        feeds = dict(x)
    else:
        heads = [n for n in order if n.op == "input"]
        if len(heads) != 1:
            raise ValueError(
                f"graph has {len(heads)} input nodes; pass a "
                "{name: array} dict instead of one array")
        feeds = {heads[0].name: x}
    env: dict[str, jax.Array] = {}
    narrow = narrow_inputs(order)
    for node in order:
        params, fn = node_runner(node, in_dtype=narrow.get(node.name))
        if node.op == "input":
            if node.name not in feeds:
                raise ValueError(f"no feed for input node {node.name!r}")
            env[node.name] = fn(params, feeds[node.name])
        else:
            env[node.name] = fn(params, *(env[s] for s in node.inputs))
    return env


def execute(graph: Graph, x) -> jax.Array:
    """Run the lowered integer graph on host (behavioural model).

    x: for conv nets (B, H, W, C); for MLPs (B, K).  Integer dtypes.
    This is the eager per-node reference; ``repro.core.engine.FusedEngine``
    compiles the same dataflow graph into one jit'd streaming executable.
    The graph's single sink is the output; branched (fan-out/fan-in) graphs
    run exactly like chains.
    """
    return trace(graph, x)[ir.graph_output(graph).name]
