"""The step pipeline: named build steps over the existing lowering passes.

FINN's ``build_dataflow`` runs a list of named transformation steps
(``build_dataflow_steps``) over the model, with optional verification
after each; this module is that machinery for our IR.  A *step* is any
callable ``step(state: BuildState)`` that mutates/returns the state (it
may also return a plain graph, which replaces ``state.graph``).  The
built-in steps wrap the module-level passes that every example used to
hand-sequence:

    validate        ir.validate_graph
    lower           lowering.lower_to_mvu
    streamline      lowering.streamline      (not in the defaults; the
                                              QAT flow opts in by name)
    finalize        lowering.finalize
    fold            lowering.apply_folding / explicit per-node Foldings
    fuse_epilogues  lowering.fuse_epilogues
    fuse_swu        lowering.fuse_swu
    tune            autotune.tune_graph      (cache hits/misses reported)
    pack_weights    lowering.pack_weights    (bit-packed weight storage)
    dataflow        dataflow.schedule -> report tables
    engine          core.engine.FusedEngine
    calibrate       serving.calibrate_cycle_time (serving target)

After every step that changed the graph, the verification hook re-runs a
probe batch through the reference interpreter (``dataflow.execute``) and
demands bit-exactness with the output captured at the first executable
graph -- FINN's per-transform verification, with
:class:`~repro.build.config.VerificationError` naming the failing step.
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter
from typing import Any, Callable

import numpy as np

from repro.build.config import (
    FOLD_BALANCE,
    FOLD_NONE,
    BuildConfig,
    BuildError,
    VerificationError,
)
from repro.build.report import BuildReport, NodeReport
from repro.core import dataflow, ir, lowering
from repro.core.ir import Graph
from repro.core.mvu import MVUConfig, MVULayer
from repro.kernels.swu_mvu import gathers_taps


# ------------------------------------------------------------------- state
@dataclasses.dataclass
class BuildState:
    """Everything a step may read or advance.

    ``graph`` is the working chain; ``ref_graph``/``probe_out`` pin the
    reference semantics the verification hook holds every later transform
    to.  Steps signal a graph rewrite via :meth:`mark_dirty` (the built-in
    steps do; custom steps that *return* a graph are marked automatically).
    """

    graph: Graph
    cfg: BuildConfig
    report: BuildReport
    cache: Any = None  # ScheduleCache once tune/calibrate need one
    engine: Any = None  # FusedEngine after the "engine" step
    calibration: dict | None = None  # cycle-time entry (serving target)
    tracer: Any = None  # repro.telemetry.Tracer when cfg.telemetry
    ref_graph: Graph | None = None
    probe: Any = None
    probe_out: np.ndarray | None = None
    _dirty: bool = False
    _engine_verified: bool = False

    def mark_dirty(self) -> None:
        self._dirty = True

    def require_cache(self):
        if self.cache is None:
            from repro.core import autotune

            self.cache = autotune.ScheduleCache()
        return self.cache


# ---------------------------------------------------------------- registry
STEP_REGISTRY: dict[str, Callable[[BuildState], Any]] = {}


def register_step(name: str):
    """Register ``fn`` under ``name`` so step lists can name it."""

    def deco(fn):
        STEP_REGISTRY[name] = fn
        fn.step_name = name
        return fn

    return deco


def step_name(step) -> str:
    if isinstance(step, str):
        return step
    return getattr(step, "step_name", getattr(step, "__name__", repr(step)))


def resolve_step(step) -> Callable[[BuildState], Any]:
    if callable(step):
        return step
    try:
        return STEP_REGISTRY[step]
    except KeyError:
        raise BuildError(
            f"unknown build step {step!r}; registered steps: "
            f"{sorted(STEP_REGISTRY)}") from None


# Default step lists per target -- the FINN ``default_build_dataflow_steps``
# analog.  ``interpret`` stops at the folded reference graph; the engine
# targets fuse + tune + compile; ``serving`` additionally measures the
# realized cycle time so batcher flush budgets are in wall-clock units.
_ENGINE_STEPS = ("validate", "lower", "finalize", "fold", "fuse_epilogues",
                 "fuse_swu", "tune", "pack_weights", "dataflow", "engine")
DEFAULT_STEPS: dict[str, tuple[str, ...]] = {
    "interpret": ("validate", "lower", "finalize", "fold", "pack_weights",
                  "dataflow"),
    "engine": _ENGINE_STEPS,
    "pipeline": _ENGINE_STEPS,
    "serving": _ENGINE_STEPS + ("calibrate",),
}


def default_steps(target: str) -> list[str]:
    """The default step-name list for one build target (copy; splice away)."""
    try:
        return list(DEFAULT_STEPS[target])
    except KeyError:
        raise BuildError(
            f"no default steps for target {target!r}; targets: "
            f"{sorted(DEFAULT_STEPS)}") from None


# ------------------------------------------------------------- built-ins
@register_step("validate")
def step_validate(state: BuildState) -> None:
    ir.validate_graph(state.graph)


@register_step("lower")
def step_lower(state: BuildState) -> None:
    """Lower to MVUs; with telemetry on, count the nodes lowered at each
    (mode, weight bits, act bits) as ``lower.<mode>.w<b>a<b>`` counters."""
    cfg = state.cfg
    state.graph = lowering.lower_to_mvu(
        state.graph, mode=cfg.mode, weight_bits=cfg.weight_bits,
        act_bits=cfg.act_bits, backend=cfg.backend)
    if state.tracer is not None:
        per_config = Counter(
            f"lower.{c.mode}.w{c.weight_bits}a{c.act_bits}"
            for c in (n.attrs["config"] for n in state.graph if n.op == "mvu"))
        for key, count in sorted(per_config.items()):
            state.tracer.counter(key, count, cat="build")
    state.mark_dirty()


@register_step("streamline")
def step_streamline(state: BuildState) -> None:
    state.graph = lowering.streamline(state.graph)
    state.mark_dirty()


@register_step("finalize")
def step_finalize(state: BuildState) -> None:
    state.graph = lowering.finalize(state.graph)
    state.mark_dirty()


@register_step("fold")
def step_fold(state: BuildState) -> None:
    cfg = state.cfg
    if isinstance(cfg.folding, str):
        if cfg.folding == FOLD_NONE:
            return
        assert cfg.folding == FOLD_BALANCE
        state.graph = lowering.apply_folding(
            state.graph, target_cycles=cfg.target_cycles,
            max_pe=cfg.max_pe, max_simd=cfg.max_simd)
        state.mark_dirty()
        return
    folds = list(cfg.folding)
    # explicit foldings apply in dataflow (topological) order -- identical
    # to list order for chains; toposorted nodes share their attrs dicts
    # with state.graph, so the in-place config rewrite reaches it
    mvu_nodes = [n for n in ir.toposort(state.graph)
                 if n.op in ("mvu", "conv_mvu")]
    if len(folds) != len(mvu_nodes):
        raise BuildError(
            f"folding override lists {len(folds)} entries but the lowered "
            f"graph has {len(mvu_nodes)} MVU stages")
    for node, fold in zip(mvu_nodes, folds):
        mcfg: MVUConfig = node.attrs["config"]
        node.attrs["config"] = MVUConfig(**{**mcfg.__dict__, "folding": fold})
    state.mark_dirty()


@register_step("fuse_epilogues")
def step_fuse_epilogues(state: BuildState) -> None:
    state.graph = lowering.fuse_epilogues(state.graph)
    state.mark_dirty()


@register_step("fuse_swu")
def step_fuse_swu(state: BuildState) -> None:
    """Collapse ``swu -> mvu`` pairs into ``conv_mvu`` nodes; with telemetry
    on, count those whose line buffer hands the MXU a whole window per dot
    (``swu_mvu.gathers_taps``) as the ``lower.conv.gathered_taps`` counter."""
    state.graph = lowering.fuse_swu(state.graph)
    if state.tracer is not None:
        gathered = sum(
            gathers_taps(n.attrs["kernel"],
                         n.attrs["config"].in_features // n.attrs["kernel"] ** 2)
            for n in state.graph if n.op == "conv_mvu")
        if gathered:
            state.tracer.counter("lower.conv.gathered_taps", gathered,
                                 cat="build")
    state.mark_dirty()


@register_step("tune")
def step_tune(state: BuildState) -> None:
    """Pin autotuned schedules; report cache hits/misses (autotune pass)."""
    cfg = state.cfg
    state.report.tune = {"mode": cfg.tune}
    if cfg.tune == "off":
        return
    from repro.core import autotune

    # run_pipeline seeds state.cache whenever cfg.tune != "off"; the cache
    # selection policy lives there alone
    kwargs = dict(cfg.tune_kwargs or {})
    keys = autotune.graph_node_keys(state.graph, device=kwargs.get("device"))
    hits = sum(1 for key in keys if key in state.cache)
    misses = len(keys) - hits
    state.graph = autotune.tune_graph(
        state.graph, cache=state.cache, mode=cfg.tune,
        allow_packed=cfg.pack != "never", **kwargs)
    state.report.tune.update(
        cache_hits=hits, cache_misses=misses, cache_entries=len(state.cache))
    state.mark_dirty()


@register_step("pack_weights")
def step_pack_weights(state: BuildState) -> None:
    """Bit-packed weight storage rewrite (``lowering.pack_weights``).

    ``pack="auto"`` packs exactly the nodes whose tuned schedule selected
    the packed datapath; ``"always"`` forces every packable node;
    ``"never"`` is a no-op.  The per-step verification hook then proves
    the rewrite bit-exact against the pinned reference for free.
    """
    cfg = state.cfg
    if cfg.pack == "never":
        return
    state.graph = lowering.pack_weights(
        state.graph, force=cfg.pack == "always")
    state.mark_dirty()


@register_step("dataflow")
def step_dataflow(state: BuildState) -> None:
    """Schedule + per-node resource tables into the report (no rewrite)."""
    sched = dataflow.schedule(state.graph)
    state.report.schedule = sched.summary() if sched.stages else {"stages": 0}
    state.report.edges = ir.edge_list(state.graph)
    branches = ir.branch_labels(state.graph)
    nodes: list[NodeReport] = []
    for node, _, out_shape in ir.io_shapes(state.graph):
        if node.op not in ("mvu", "conv_mvu"):
            continue
        mcfg: MVUConfig = node.attrs["config"]
        px = ir.n_pixels(out_shape)
        fold = mcfg.resolved_folding()
        res = MVULayer(mcfg).resources(n_pixels=px)
        nodes.append(NodeReport(
            name=node.name, op=node.op, mode=mcfg.mode,
            n=mcfg.out_features, k=mcfg.in_features,
            pe=fold.pe, simd=fold.simd, n_pixels=px, cycles=res.cycles,
            lut_bytes=res.lut_bytes, ff_bytes=res.ff_bytes,
            bram_bytes=res.bram_bytes, backend=mcfg.backend,
            tuned=mcfg.blocks is not None,
            inputs=list(node.inputs),
            branch=branches.get(node.name, "main"),
            packed=mcfg.packed,
            weight_bytes=res.weight_bytes,
            canonical_weight_bytes=res.canonical_weight_bytes))
    state.report.nodes = nodes
    if sched.stages:
        state.report.predicted_interval_s = (
            sched.steady_state_interval / dataflow.DEFAULT_CLOCK_HZ)
        measured = _measured_interval(state, sched)
        if measured is not None:
            state.report.measured_interval_s = measured
            state.report.cycle_time_source = "measured"


def _measured_interval(state: BuildState, sched) -> float | None:
    """Measured-cycle-time interval when the cache holds a calibration.

    The conversion itself stays in :func:`dataflow.interval_seconds` (the
    single owner of the cycles-to-seconds rule); this helper only decides
    whether a measurement exists at all.
    """
    if state.cache is None:
        return None
    from repro.core import autotune

    ent = state.cache.get(autotune.cycle_time_key())
    if ent is None or not ent.get("s_per_cycle"):
        return None
    return dataflow.interval_seconds(sched, cache=state.cache)


@register_step("engine")
def step_engine(state: BuildState) -> None:
    """Compile the fused streaming engine (tuned microbatch tile applies
    through the shared cache)."""
    from repro.core.engine import FusedEngine

    cfg = state.cfg
    state.engine = FusedEngine(
        state.graph, microbatches=cfg.microbatches, tune=cfg.tune,
        cache=state.cache, tune_kwargs=cfg.tune_kwargs)
    if cfg.tune != "off":
        state.report.tune["engine_tile"] = state.engine._tile


@register_step("calibrate")
def step_calibrate(state: BuildState) -> None:
    """Measure the realized seconds-per-cycle (the serving warmup path):
    recorded under ``autotune.cycle_time_key`` in the build's cache so
    every batcher constructed from this Accelerator budgets flushes in
    measured wall-clock units, not the nominal clock."""
    from repro.serving import calibrate_cycle_time

    if state.engine is None:
        raise BuildError("the 'calibrate' step needs the 'engine' step first")
    cfg = state.cfg
    state.calibration = calibrate_cycle_time(
        state.engine, batch=cfg.calibrate_batch, reps=cfg.calibrate_reps,
        cache=state.require_cache())
    sched = state.engine.schedule
    if sched.stages:
        state.report.measured_interval_s = dataflow.interval_seconds(
            sched, cache=state.cache)
        state.report.cycle_time_source = "measured"


# ------------------------------------------------------------ verification
def _localize_divergence(state: BuildState, graph: Graph) -> tuple:
    """Pin a probe-batch divergence to its first bad node and branch path.

    Re-traces ``graph`` and the pinned reference graph node-by-node
    (``dataflow.trace``) and walks the current graph in dataflow order
    comparing each node's stream against the reference activation it must
    reproduce -- fused nodes against the last epilogue node they absorbed
    (``attrs["fused"]``), conv_mvu nodes against their pre-``fuse_swu``
    MVU.  Returns ``(detail_suffix, node_name, branch)``; all empty when
    localization itself fails (the step-level error still raises).
    """
    try:
        ref_env = dataflow.trace(state.ref_graph, state.probe)
        got_env = dataflow.trace(graph, state.probe)
        branches = ir.branch_labels(graph)
    except Exception:
        return "", None, None
    for node in ir.toposort(graph):
        if node.op == "input":
            continue
        cands = []
        fused = node.attrs.get("fused")
        if fused:
            cands.append(fused[-1])
        cands.append(node.name)
        if ".conv_mvu" in node.name:
            cands.append(node.name.replace(".conv_mvu", ".mvu"))
        want = next((ref_env[c] for c in cands if c in ref_env), None)
        got = got_env.get(node.name)
        if want is None or got is None:
            continue
        want, got = np.asarray(want), np.asarray(got)
        if got.shape != want.shape or not np.array_equal(got, want):
            br = branches.get(node.name, "main")
            return (f"; first divergent node: {node.name!r} on branch "
                    f"{br!r}", node.name, br)
    return "", None, None


def _executable(graph: Graph) -> bool:
    """Can ``dataflow.execute`` run this graph? (no float conv/linear left,
    every MVU finalized)."""
    for n in graph:
        if n.op in ("conv", "linear"):
            return False
        if n.op in ("mvu", "conv_mvu") and "mvu" not in n.params:
            return False
    return True


def _op_histogram(graph: Graph) -> dict[str, int]:
    return dict(Counter(n.op for n in graph))


def verify_after(state: BuildState, name: str) -> bool | None:
    """The per-step verification hook (FINN's verification steps).

    Captures the reference interpreter output at the first executable
    graph; every later graph rewrite must reproduce it bit-exactly on the
    probe batch, and the compiled engine is held to the same reference.
    Returns True (verified), False is never returned -- a mismatch raises
    :class:`VerificationError` naming the step -- and None when there was
    nothing new to verify.
    """
    verified = None
    if state._dirty and _executable(state.graph):
        state._dirty = False
        if state.probe is None:
            from repro.core import autotune

            state.probe = autotune.synth_input(
                state.graph, state.cfg.probe_batch, seed=state.cfg.seed)
        if state.probe_out is None:
            # first executable graph: pin the reference semantics (and keep
            # this graph as the Accelerator's interpreter facing)
            state.ref_graph = state.graph
            state.probe_out = np.asarray(
                dataflow.execute(state.graph, state.probe))
            verified = True
        else:
            got = np.asarray(dataflow.execute(state.graph, state.probe))
            if got.shape != state.probe_out.shape or not np.array_equal(
                    got, state.probe_out):
                suffix, bad_node, branch = _localize_divergence(
                    state, state.graph)
                raise VerificationError(
                    name, "graph output diverged from the reference "
                    f"interpreter on a {state.cfg.probe_batch}-sample probe "
                    f"batch{suffix}", node=bad_node, branch=branch)
            verified = True
    if state.engine is not None and not state._engine_verified \
            and state.probe_out is not None:
        state._engine_verified = True
        got = np.asarray(state.engine(state.probe))
        if not np.array_equal(got, state.probe_out):
            # the engine shares the fused graph's params, so an eager
            # re-trace of engine.graph localizes the divergent stage
            suffix, bad_node, branch = _localize_divergence(
                state, state.engine.graph)
            raise VerificationError(
                name, "compiled engine diverged from the reference "
                f"interpreter on the probe batch{suffix}",
                node=bad_node, branch=branch)
        verified = True
    return verified


# ------------------------------------------------------------------ driver
def run_pipeline(graph: Graph, cfg: BuildConfig) -> BuildState:
    """Execute the config's step list over ``graph``; returns the final
    state (the :class:`~repro.build.accelerator.Accelerator` wraps it)."""
    report = BuildReport(name=cfg.name, target=cfg.target,
                         config=cfg.snapshot())
    state = BuildState(graph=list(graph), cfg=cfg, report=report)
    if cfg.tune != "off":
        from repro.core import autotune

        state.cache = cfg.cache if cfg.cache is not None else autotune.default_cache()
    elif cfg.cache is not None:
        state.cache = cfg.cache
    tracer = None
    if cfg.telemetry:
        from repro.telemetry import Tracer

        tracer = state.tracer = Tracer(meta={"build": cfg.name,
                                             "target": cfg.target})
    steps = cfg.steps if cfg.steps is not None else DEFAULT_STEPS[cfg.target]
    t_build = time.perf_counter()
    for step in steps:
        fn = resolve_step(step)
        name = step_name(step)
        sp = (tracer.span(f"step.{name}", cat="build").__enter__()
              if tracer is not None else None)
        t0 = time.perf_counter()
        try:
            out = fn(state)
        finally:
            if sp is not None:
                sp.__exit__(None, None, None)
        if isinstance(out, BuildState):
            state = out
        elif isinstance(out, list):  # a custom step returned a graph
            state.graph = out
            state.mark_dirty()
        wall = time.perf_counter() - t0
        verified = (verify_after(state, name)
                    if cfg.verify != "off" else None)
        report.record_step(name, wall, verified, _op_histogram(state.graph))
    report.total_wall_s = time.perf_counter() - t_build
    if tracer is not None:
        report.telemetry = tracer.summary()
    if state.ref_graph is None and _executable(state.graph):
        state.ref_graph = state.graph
    return state
