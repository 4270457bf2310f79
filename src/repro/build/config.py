"""Build configuration + error types for the step-pipeline compiler.

``BuildConfig`` is the single declarative knob set for
:func:`repro.build.build` -- the FINN ``DataflowBuildConfig`` analog.  One
config names a *target* (which default step list runs), the lowering
parameters every step shares, the folding / autotune policy, and the
verification + report policy.  Everything here is plain data; the step
functions in :mod:`repro.build.steps` read it, never mutate it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

from repro.core.folding import Folding

TARGETS = ("interpret", "engine", "pipeline", "serving")
TUNE_MODES = ("off", "cache", "auto")
VERIFY_MODES = ("all", "off")
# weight-packing policies (the pack_weights step):
#   auto   pack nodes whose tuned schedule selected the packed datapath
#   never  keep canonical weight storage everywhere
#   always force packed storage on every packable node (sweeps/benchmarks)
PACK_MODES = ("auto", "never", "always")

# folding policies (the ``folding`` field also accepts an explicit
# per-MVU-node list of Folding objects, applied in chain order)
FOLD_BALANCE = "balance"  # rate-balance all stages (lowering.apply_folding)
FOLD_NONE = "none"  # keep the per-layer heuristic defaults


class BuildError(ValueError):
    """A build step could not run (bad config, malformed graph, ...)."""


class VerificationError(BuildError):
    """A step's output diverged from the reference interpreter.

    The message always names the offending step -- FINN's verification
    steps fail the build the same way, pointing at the transform that
    broke numerical equivalence.  When the hook can localize the
    divergence by re-tracing the graph node-by-node, ``node`` holds the
    first divergent node's id and ``branch`` its branch path (which arm
    of a fan-out it sits on), and the message names both.
    """

    def __init__(self, step: str, detail: str, *,
                 node: str | None = None, branch: str | None = None):
        self.step = step
        self.node = node
        self.branch = branch
        super().__init__(f"verification failed after step {step!r}: {detail}")


@dataclasses.dataclass(frozen=True)
class BuildConfig:
    """Declarative build recipe consumed by :func:`repro.build.build`.

    target: which Accelerator facing the build produces --
        ``interpret`` (eager reference only), ``engine`` (FusedEngine),
        ``pipeline`` (engine + multi-device ``as_pipeline``), ``serving``
        (engine + measured cycle-time calibration for the batcher).
    mode / weight_bits / act_bits / backend: lowering parameters
        (``lowering.lower_to_mvu``); mode and weight_bits are defaults
        that a conv or linear node's own attrs override, so each MVU gets
        its own ``MVUConfig``.
    folding: ``"balance"`` rate-balances every stage
        (``lowering.apply_folding`` with ``target_cycles``/``max_pe``/
        ``max_simd``), ``"none"`` keeps heuristic per-layer defaults, or an
        explicit sequence of :class:`Folding`, one per MVU node in chain
        order (the paper's Table 6 PE/SIMD choices).
    tune: autotune policy -- ``"off"``, ``"cache"`` (committed schedules,
        zero measurement) or ``"auto"`` (measure misses).  ``cache`` may
        hold a ScheduleCache; None means ``autotune.default_cache()``.
    pack: weight-packing policy for the ``pack_weights`` step --
        ``"auto"`` packs exactly the nodes whose tuned schedule selected
        the packed datapath, ``"never"`` keeps canonical storage,
        ``"always"`` forces packed storage on every packable node.
    verify: ``"all"`` re-runs a probe batch through the reference
        interpreter after every graph transform (FINN's verification
        steps) and checks bit-exactness; ``"off"`` skips.
    steps: override the target's default step list with names from the
        step registry and/or custom callables ``step(state) -> state``.
    name / output_dir: report identity; when ``output_dir`` is set the
        BuildReport is serialized to ``<output_dir>/<name>_build_report
        .json`` (next to the autotune cache under ``experiments/``).
    graph: optional -- lets ``build(config)`` be called with the config
        alone (``build(graph, config)`` wins when both are given).
    """

    target: str = "engine"
    # lowering
    mode: str = "standard"
    weight_bits: int = 4
    act_bits: int = 4
    backend: str = "pallas"
    # folding
    folding: Sequence[Folding] | str = FOLD_BALANCE
    target_cycles: int | None = None
    max_pe: int = 128
    max_simd: int = 128
    # autotune
    tune: str = "off"
    cache: Any = None  # ScheduleCache | None
    tune_kwargs: dict | None = None
    # weight packing (the pack_weights step)
    pack: str = "auto"
    # engine
    microbatches: int | None = None
    # serving calibration (target="serving")
    calibrate_batch: int = 32
    calibrate_reps: int = 3
    # verification + report
    verify: str = "all"
    # telemetry: trace every build step with a repro.telemetry.Tracer and
    # embed the span summary in the BuildReport (zero cost when False)
    telemetry: bool = False
    probe_batch: int = 8
    seed: int = 0
    steps: Sequence[Any] | None = None
    name: str = "build"
    output_dir: str | None = None
    graph: Any = None

    def __post_init__(self):
        if self.target not in TARGETS:
            raise BuildError(f"target must be one of {TARGETS}, got {self.target!r}")
        if self.tune not in TUNE_MODES:
            raise BuildError(f"tune must be one of {TUNE_MODES}, got {self.tune!r}")
        if self.verify not in VERIFY_MODES:
            raise BuildError(
                f"verify must be one of {VERIFY_MODES}, got {self.verify!r}")
        if self.pack not in PACK_MODES:
            raise BuildError(
                f"pack must be one of {PACK_MODES}, got {self.pack!r}")
        if isinstance(self.folding, str) and self.folding not in (
                FOLD_BALANCE, FOLD_NONE):
            raise BuildError(
                f"folding must be {FOLD_BALANCE!r}, {FOLD_NONE!r} or a "
                f"sequence of Folding, got {self.folding!r}")

    def snapshot(self) -> dict:
        """JSON-safe view of the config for the BuildReport (graph, cache
        and callables are identified, not serialized)."""
        d = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name in ("graph", "cache"):
                d[f.name] = None if v is None else type(v).__name__
            elif f.name == "steps":
                d[f.name] = None if v is None else [
                    s if isinstance(s, str) else getattr(s, "__name__", repr(s))
                    for s in v]
            elif f.name == "folding" and not isinstance(v, str):
                d[f.name] = [[fold.pe, fold.simd] for fold in v]
            else:
                d[f.name] = v
        return d
