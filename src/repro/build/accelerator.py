"""The Accelerator facade: one object per built dataflow design.

Today's entry points are scattered: the eager interpreter lives in
``repro.core.dataflow``, the fused engine in ``repro.core.engine``, the
continuous batcher in ``repro.serving``, and the multi-device pipeline on
the engine itself.  ``Accelerator`` (the FINN "bitfile + driver" analog)
unifies them behind the build:

    acc = repro.build.build(graph, target="serving", ...)
    y   = acc.interpret(x)     # eager reference (bit-exact contract)
    y   = acc(x)               # fused streaming engine
    b   = acc.serve(batch_buckets=(1, 8, 32))   # continuous batcher
    run = acc.as_pipeline(mesh)                  # multi-device pipeline
    acc.report                  # the BuildReport (JSON-serializable)
"""

from __future__ import annotations

import os

from repro.build.config import BuildError
from repro.build.report import BuildReport
from repro.build.steps import BuildState
from repro.core import dataflow


class Accelerator:
    """A built dataflow design: interpreter + engine + serving, one handle.

    Constructed by :func:`repro.build.build`; never directly.  ``graph`` is
    the final (fused, tuned) chain, ``ref_graph`` the first executable
    snapshot the verification hooks pinned -- the unfused reference the
    benchmarks time the engine against.
    """

    def __init__(self, state: BuildState):
        self.config = state.cfg
        self.graph = state.graph
        self.ref_graph = state.ref_graph if state.ref_graph is not None else state.graph
        self.report: BuildReport = state.report
        self.cache = state.cache
        self.calibration = state.calibration
        # build-step Tracer when cfg.telemetry was set (None otherwise);
        # its summary is already embedded in report.telemetry
        self.tracer = state.tracer
        self._engine = state.engine
        if self.config.output_dir:
            self.save_report()

    # -------------------------------------------------------------- compute
    @property
    def engine(self):
        """The compiled :class:`~repro.core.engine.FusedEngine`."""
        if self._engine is None:
            raise BuildError(
                f"this build (target={self.config.target!r}) ran no 'engine' "
                "step; rebuild with target='engine'/'pipeline'/'serving' or "
                "a step list containing 'engine'")
        return self._engine

    def interpret(self, x):
        """Eager reference semantics (``dataflow.execute``): one dispatch
        per node on the unfused graph -- the behavioural model every
        verification hook compared against."""
        return dataflow.execute(self.ref_graph, x)

    def __call__(self, x):
        return self.engine(x) if self._engine is not None else self.interpret(x)

    def dispatch(self, x, *, params=None, tracer=None):
        """Non-blocking engine submit (see ``FusedEngine.dispatch``)."""
        return self.engine.dispatch(x, params=params, tracer=tracer)

    def drift_monitor(self, **kwargs):
        """A :class:`~repro.telemetry.DriftMonitor` primed with this
        build's per-stage predicted intervals (stage cycles x the
        *calibrated* cycle time).  Requires a ``target="serving"`` build
        (or any step list that ran ``calibrate``): against the nominal
        clock the measured/predicted ratios are meaningless -- see
        docs/observability.md."""
        from repro.telemetry import DriftMonitor

        s_per_cycle = (self.calibration or {}).get("s_per_cycle")
        if not s_per_cycle:
            raise BuildError(
                "drift_monitor() needs a calibrated cycle time; rebuild "
                "with target='serving' (the 'calibrate' step) so per-stage "
                "predictions reflect measured seconds, not the nominal clock")
        return DriftMonitor.from_schedule(
            self.schedule, float(s_per_cycle), **kwargs)

    @property
    def schedule(self):
        return (self._engine.schedule if self._engine is not None
                else dataflow.schedule(self.graph))

    def plan(self, batch: int):
        return self.engine.plan(batch)

    # -------------------------------------------------------------- serving
    def serve(self, *, warmup: bool = True, cache=None,
              fault_policy=None, faults=None, **kwargs):
        """A :class:`~repro.serving.batcher.ContinuousBatcher` over the
        engine.  The build's cache (holding the calibrated cycle time when
        the ``serving`` target ran) feeds the flush budgets unless an
        explicit ``cache`` overrides it; ``warmup`` precompiles every
        bucket shape on every replica before traffic arrives.

        ``fault_policy`` (a :class:`~repro.serving.health.FaultPolicy`)
        tunes the failure handling -- retries, dispatch timeouts, hedging,
        the integrity guard and brownout; the default policy is enabled
        with conservative settings and adds no overhead while replicas are
        healthy.  ``faults`` injects a deterministic
        :class:`~repro.serving.faults.FaultPlan` (chaos testing only).
        ``tracer=``/``drift=`` (forwarded to the batcher) wire telemetry:
        pair with :meth:`drift_monitor` for calibrated predictions."""
        from repro.serving import ContinuousBatcher

        batcher = ContinuousBatcher(
            self.engine, cache=cache if cache is not None else self.cache,
            fault_policy=fault_policy, faults=faults, **kwargs)
        return batcher.warmup() if warmup else batcher

    # ------------------------------------------------------------- pipeline
    def as_pipeline(self, mesh, *, axis: str = "stage", tracer=None):
        """Map the stage chain onto a device mesh (``FusedEngine.as_pipeline``)."""
        return self.engine.as_pipeline(mesh, axis=axis, tracer=tracer)

    # --------------------------------------------------------------- report
    def report_path(self) -> str:
        out_dir = self.config.output_dir or "."
        return os.path.join(out_dir, f"{self.config.name}_build_report.json")

    def save_report(self, path: str | None = None) -> str:
        """Serialize the BuildReport (default: ``<output_dir>/<name>_
        build_report.json``, next to the autotune cache artifacts)."""
        return self.report.save(path if path is not None else self.report_path())
