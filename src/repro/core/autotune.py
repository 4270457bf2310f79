"""Empirical folding autotuner: design-space search over Pallas tile schedules.

The paper's central exercise is a *sweep*: PE x SIMD folding and weight
codings are enumerated per layer and the hand-scheduled implementation wins
exactly when its schedule matches the problem size.  The runtime analog of
that sweep lives here.  Instead of picking every kernel schedule from the
one-shot ``choose_folding`` + ``to_tpu_blocks`` heuristic (frozen
``block_m=128 / block_n=128 / block_k=512`` style defaults that pad small
layers up to full MXU tiles), the autotuner

  1. enumerates candidate schedules per MVU/conv node from the layer's
     folding divisors (``folding.block_candidates``) plus the
     pallas-vs-xla backend axis,
  2. prunes them with the analytic resource model: candidates whose VMEM
     working set exceeds the budget are rejected outright, the survivors
     are ordered by predicted cycles so measurement starts from the
     model's best guess,
  3. measures the shortlist with the paired interleaved timer
     (``benchmarks/common.py``) against the heuristic schedule, keeping
     only bit-exact winners,
  4. records winners in a persistent JSON cache keyed by
     ``(device kind, op/conv-geometry, mode, N, K, epilogue form,
     n_pixels)``.

``tune_graph`` annotates every node of a lowered graph with its tuned
blocks; ``FusedEngine(tune="cache")`` consumes committed results with zero
measurement at load time, ``tune="auto"`` fills misses by measuring.
``tune_engine`` extends the search one level up: the engine's microbatch
tile is itself a design dimension (FINN's FIFO depth analog) and gets its
own cache entry keyed by the graph signature.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time

import jax
import numpy as np

from repro.core import ir
from repro.core.folding import (
    Folding,
    block_candidates,
    divisors,
    legal_blocks,
    steps_in_words,
)
from repro.core.ir import Graph, Node
from repro.core.mvu import KernelBlocks, MVUConfig
from repro.core.resource_model import VMEM_BYTES, mvu_resources
from repro.core.swu import out_dim
from repro.kernels import ops, packing
from repro.kernels.packing import WORD_BITS
from repro.kernels.swu_mvu import conv_vmem_bytes, fit_conv_blocks

CACHE_VERSION = 1
# user-side persistent cache (the committed defaults ship in repro.configs)
DEFAULT_CACHE_PATH = os.path.join("experiments", "autotune", "cache.json")
CACHE_PATH_ENV = "REPRO_AUTOTUNE_CACHE"


# --------------------------------------------------------------------- keys
def device_kind() -> str:
    """Stable schedule-cache device key from the first device's kind, e.g.
    ``cpu`` or ``tpu-v5-lite`` (a v5e reports ``TPU v5 lite``)."""
    return str(jax.devices()[0].device_kind).strip().lower().replace(" ", "-")


def epilogue_form(params) -> str:
    """``thresh`` / ``scale`` / ``raw`` -- the MVTU epilogue variant."""
    if params is None:
        return "raw"
    if getattr(params, "thresholds", None) is not None:
        return "thresh"
    if getattr(params, "out_scale", None) is not None:
        return "scale"
    return "raw"


def op_tag(node: Node, in_shape: tuple | None = None) -> str:
    """Distinguish op kind and conv geometry in cache keys.

    Dense nodes are all ``mvu``; conv nodes with the same (mode, N, K,
    n_pixels) can still differ in kernel/stride/pad and the resident input
    image -- the schedule tuned (and VMEM-pruned) for one geometry must not
    be applied to another.
    """
    if node.op != "conv_mvu":
        return "mvu"
    kd, st, pd = node.attrs["kernel"], node.attrs["stride"], node.attrs["pad"]
    hwc = "x".join(str(d) for d in (in_shape or ()))
    return f"conv{kd}s{st}p{pd}@{hwc}"


def node_key(cfg: MVUConfig, *, epilogue: str = "raw", n_pixels: int = 1,
             device: str | None = None, op: str = "mvu") -> str:
    # None = the live host; "" is a valid (device-less) scope used by
    # engine_key's digest parts and must NOT fall back to device_kind()
    device = device_kind() if device is None else device
    key = "|".join([
        device, op, cfg.mode, f"n{cfg.out_features}", f"k{cfg.in_features}",
        epilogue, f"px{n_pixels}",
    ])
    # packed-datapath configs get their own key space: a schedule tuned for
    # bit-packed weight storage must never alias the canonical one.  The
    # suffix is appended only when packed, so every committed (unpacked)
    # cache entry and engine digest stays valid.
    return key + "|packed" if cfg.packed else key


def graph_node_keys(graph: Graph, *, device: str | None = None) -> list[str]:
    """Schedule-cache keys for every tunable node of a lowered graph.

    One key per finalized ``mvu``/``conv_mvu`` node, in chain order -- the
    exact keys :func:`tune_graph` will look up (same shape propagation,
    same epilogue/op tagging).  The build pipeline's cache-hit accounting
    and the design-space explorer's warm-sweep assertions both consume
    this instead of re-deriving the key recipe.
    """
    keys: list[str] = []
    for node, ins, out_shape in ir.io_shapes(graph):
        if node.op not in ("mvu", "conv_mvu") or "mvu" not in node.params:
            continue
        in_shape = ins[0] if ins else None
        keys.append(node_key(
            node.attrs["config"],
            epilogue=epilogue_form(node.params["mvu"]),
            n_pixels=ir.n_pixels(out_shape), device=device,
            op=op_tag(node, in_shape)))
    return keys


def cycle_time_key(device: str | None = None) -> str:
    """Cache key for the measured wall-clock seconds per schedule cycle.

    Recorded by ``repro.serving.batcher.calibrate_cycle_time``; consumed by
    ``dataflow.interval_seconds`` to turn the steady-state interval into the
    serving batcher's flush time budget.
    """
    device = device_kind() if device is None else device
    return f"cycletime|{device}"


def engine_key(graph: Graph, *, device: str | None = None) -> str:
    """Cache key for engine-level (microbatch) tuning of one stage chain.

    The digest is built from device-less node keys, so the same graph gets
    the same digest on every host and only the ``engine|<device>|`` prefix
    scopes the entry -- a ``device`` override therefore resolves entries
    recorded on another machine.
    """
    device = device_kind() if device is None else device
    parts = []
    for node, ins, out_shape in ir.io_shapes(graph):
        if node.op in ("mvu", "conv_mvu") and "mvu" in node.params:
            cfg = node.attrs["config"]
            in_shape = ins[0] if ins else None
            parts.append(node_key(cfg, epilogue=epilogue_form(node.params["mvu"]),
                                  n_pixels=ir.n_pixels(out_shape), device="",
                                  op=op_tag(node, in_shape)))
    digest = hashlib.sha1("~".join(parts).encode()).hexdigest()[:12]
    return f"engine|{device}|{digest}"


# -------------------------------------------------------------------- cache
class ScheduleCache:
    """Persistent key -> schedule-entry store (JSON on disk).

    Entries are plain dicts (backend + block shapes + bookkeeping) so the
    cache file diffs cleanly and can be committed / uploaded as a CI
    artifact.  ``merge`` lets the committed per-config defaults
    (``repro.configs.*.TUNED_SCHEDULES``) and a user cache coexist.
    """

    def __init__(self, entries: dict | None = None, path: str | None = None):
        self.entries: dict[str, dict] = {k: dict(v) for k, v in (entries or {}).items()}
        self.path = path

    def get(self, key: str) -> dict | None:
        return self.entries.get(key)

    def put(self, key: str, entry: dict) -> None:
        self.entries[key] = dict(entry)

    def merge(self, other: "ScheduleCache") -> "ScheduleCache":
        self.entries.update({k: dict(v) for k, v in other.entries.items()})
        return self

    def __len__(self) -> int:
        return len(self.entries)

    def __contains__(self, key: str) -> bool:
        return key in self.entries

    @classmethod
    def load(cls, path: str) -> "ScheduleCache":
        with open(path) as f:
            payload = json.load(f)
        if payload.get("version") != CACHE_VERSION:
            raise ValueError(
                f"autotune cache {path} has version {payload.get('version')!r}, "
                f"expected {CACHE_VERSION}")
        return cls(payload.get("entries", {}), path=path)

    def save(self, path: str | None = None) -> str:
        path = path or self.path
        if path is None:
            raise ValueError("no cache path to save to")
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": self.entries},
                      f, indent=2, sort_keys=True)
            f.write("\n")
        self.path = path
        return path


def default_cache() -> ScheduleCache:
    """Committed tuned defaults (configs) + an opt-in user cache.

    The per-config ``TUNED_SCHEDULES`` dicts ship in the package (zero I/O,
    zero measurement to consume).  A user cache file overrides them only
    when ``$REPRO_AUTOTUNE_CACHE`` names one, so a default run reads
    nothing but committed files; the named path is also where the cache
    saves.
    """
    cache = ScheduleCache()
    from repro.configs import cnv_bnn, nid_mlp

    for mod in (nid_mlp, cnv_bnn):
        cache.merge(ScheduleCache(getattr(mod, "TUNED_SCHEDULES", {})))
    path = os.environ.get(CACHE_PATH_ENV)
    if path:
        if os.path.exists(path):
            cache.merge(ScheduleCache.load(path))
        cache.path = path
    return cache


# --------------------------------------------------------------- candidates
@dataclasses.dataclass(frozen=True)
class Candidate:
    backend: str
    blocks: KernelBlocks
    predicted_cycles: int
    vmem_bytes: int
    packed: bool = False  # bit-packed weight storage + packed kernel family

    def entry(self, **extra) -> dict:
        out = {
            "backend": self.backend,
            **dataclasses.asdict(self.blocks),
            "predicted_cycles": int(self.predicted_cycles),
            **extra,
        }
        if self.packed:  # legacy (unpacked) entries stay byte-identical
            out["packed"] = True
        return out


def _blocks_folding(blocks: KernelBlocks, mode: str,
                    packed: bool = False) -> Folding:
    """The folding a block schedule *acts* as (PE=block_n, SIMD=K step)."""
    if steps_in_words(mode, packed):
        simd = blocks.block_kw * WORD_BITS
    else:
        simd = blocks.block_k
    return Folding(blocks.block_n, simd)


def packable(cfg: MVUConfig) -> bool:
    """Whether the packed datapath exists for this config's weight coding.

    All 1-bit codings pack into uint32 bitplanes; standard weights pack
    into 2-bit lanes only when they actually fit signed 2 bits.
    """
    return cfg.mode in ("xnor", "binary") or cfg.weight_bits <= 2


def natively_packed(cfg: MVUConfig, backend: str) -> bool:
    """Whether this (coding, backend) kernel already IS the packed datapath.

    The xnor Pallas kernel consumes packed uint32 words for both operands
    (the paper's Fig. 4a XNOR/popcount array) -- its candidates carry
    ``packed=True`` so the tuned entry records the datapath that actually
    ran, and the canonical comparator stays the unpack+matmul XLA path.
    """
    return cfg.mode == "xnor" and backend == "pallas"


def enumerate_candidates(
    cfg: MVUConfig,
    *,
    n_pixels: int = 1,
    n_thresh: int = 0,
    in_shape: tuple | None = None,
    conv: dict | None = None,
    vmem_bytes: int = VMEM_BYTES,
    max_measure: int = 8,
) -> list[Candidate]:
    """Model-pruned, cycle-ordered shortlist for one node.

    Every candidate whose VMEM working set exceeds ``vmem_bytes`` is
    rejected; the survivors are ordered by the analytic cycle model (best
    guess first) and capped at ``max_measure`` pallas schedules.  The
    heuristic schedule and the XLA backend are always appended so the
    search space contains the status quo and the compiler path.
    """
    n, k = cfg.out_features, cfg.in_features
    cands: list[Candidate] = []
    if conv is not None:
        # fused conv kernel: full-K dot per step; the schedule is block_n x
        # rows_per_tile (block_m only acts through the derived row tile, so
        # it is pinned explicitly on the candidate).  Both are rounded to
        # the chip's layout, so equal tiles appear once.
        h, w, c = in_shape
        oh = out_dim(h, conv["kernel"], conv["stride"], conv["pad"])
        ow = out_dim(w, conv["kernel"], conv["stride"], conv["pad"])
        seen: set[tuple[int, int]] = set()
        for bm in (32, 128, 256):
            for want_bn in sorted(set(divisors(n) + [128])):
                rt, bn = fit_conv_blocks(oh, ow, n, block_m=bm,
                                         block_n=want_bn).values()
                if bn > 512 or (rt, bn) in seen:
                    continue
                seen.add((rt, bn))
                vm = conv_vmem_bytes(
                    h, w, c, n, k, kernel=conv["kernel"], stride=conv["stride"],
                    pad=conv["pad"], block_m=bm, block_n=bn, n_thresh=n_thresh)
                blocks = KernelBlocks(block_m=bm, block_n=bn, rows_per_tile=rt)
                cyc = Folding(bn, k).cycles(n, k, n_pixels)
                cands.append(Candidate("pallas", blocks, cyc, vm))
    else:
        # joint folding x packing space: each legal tile schedule exists
        # once per weight-storage form the coding supports (the xnor Pallas
        # kernel is natively packed, so its packed variant would duplicate)
        packed_axes = [False]
        if packable(cfg) and cfg.mode != "xnor":
            packed_axes.append(True)
        for pk in packed_axes:
            for blk in block_candidates(n, k, cfg.mode, packed=pk):
                blocks = KernelBlocks.from_blocks(blk)
                fold = _blocks_folding(blocks, cfg.mode, pk)
                res = mvu_resources(
                    n, k, fold, mode=cfg.mode, weight_bits=cfg.weight_bits,
                    act_bits=cfg.act_bits, n_pixels=n_pixels,
                    block_m=blocks.block_m, n_thresh=n_thresh,
                    blocks=blocks.as_kwargs(cfg.mode, pk), packed=pk)
                cands.append(Candidate(
                    "pallas", blocks, res.cycles, res.lut_bytes,
                    packed=pk or natively_packed(cfg, "pallas")))

    survivors = [c for c in cands if c.vmem_bytes <= vmem_bytes]
    survivors.sort(key=lambda c: (c.predicted_cycles, c.vmem_bytes))
    survivors = survivors[:max_measure]

    heur = KernelBlocks.from_blocks(
        {**{"block_m": cfg.block_m}, **cfg.kernel_blocks()})
    heur_cycles = cfg.resolved_folding().cycles(n, k, n_pixels)
    if not any(c.blocks == heur for c in survivors):
        survivors.append(Candidate("pallas", heur, heur_cycles, 0,
                                   packed=natively_packed(cfg, "pallas")))
    # the XLA backend is one more point in the design space: on hosts where
    # the compiler's schedule beats interpret-mode Pallas (every CPU), the
    # empirical search must be allowed to find that out.
    survivors.append(Candidate("xla", heur, heur_cycles, 0))
    if conv is None and packable(cfg):
        # ... and so is the packed datapath compiled by XLA (the blocked
        # XNOR popcount path in particular is the memory-bandwidth-bound
        # fast path on large N*K layers) -- always in the measured set so
        # the packed-vs-unpacked decision is empirical, never assumed.
        survivors.append(Candidate("xla", heur, heur_cycles, 0, packed=True))
    return survivors


# -------------------------------------------------------------------- timer
def paired_times(fn_a, fn_b, *args, reps: int = 3, warmup: int = 1):
    """Paired interleaved A/B timer: ``(t_a, t_b, speedup_of_b_over_a)``.

    Each rep times both callables back-to-back, so environmental slowdowns
    (noisy CI neighbors, frequency scaling) hit both sides of the ratio;
    the reported speedup is the median of per-rep ratios, and the times are
    the per-side minima (the stable one-sided-noise estimator).  This is
    the single canonical estimator -- ``benchmarks.common`` re-exports it,
    so the tuner and the CI regression gate always measure the same way.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn_a(*args))
        jax.block_until_ready(fn_b(*args))
    tas, tbs, ratios = [], [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a(*args))
        ta = time.perf_counter() - t0
        t1 = time.perf_counter()
        jax.block_until_ready(fn_b(*args))
        tb = time.perf_counter() - t1
        tas.append(ta)
        tbs.append(tb)
        ratios.append(ta / tb)
    return float(np.min(tas)), float(np.min(tbs)), float(np.median(ratios))


# the name tune_node/tune_engine resolve (and tests stub) at call time
paired_timer = paired_times


# -------------------------------------------------------------- measurement
def _synth_activations(cfg: MVUConfig, m: int, in_shape: tuple | None,
                       conv: dict | None, seed: int = 0):
    rng = np.random.default_rng(seed)
    import jax.numpy as jnp

    if conv is not None:
        # one image: the engine streams conv stages in single-image
        # microbatches (the conv bottleneck sets tile=1), so candidates
        # must be measured in that regime, not on a large batch
        h, w, c = in_shape
        lo, hi = (0, 2) if cfg.mode == "xnor" else (0, 2**cfg.act_bits)
        # int8 where it fits, as the engine narrows such streams
        return jnp.asarray(rng.integers(lo, hi, (1, h, w, c)),
                           jnp.int8 if hi <= 128 else jnp.int32)
    k = cfg.in_features
    if cfg.mode == "xnor":
        bits = jnp.asarray(rng.integers(0, 2, (m, k)), jnp.int32)
        return packing.pack_bits(bits)
    if cfg.mode == "binary":
        return jnp.asarray(rng.integers(-8, 8, (m, k)), jnp.int8)
    return jnp.asarray(rng.integers(-8, 8, (m, k)), jnp.int8)


def _node_fn(cfg: MVUConfig, params, cand: Candidate, conv: dict | None):
    blocks = cand.blocks.as_kwargs(cfg.mode, cand.packed)
    if conv is not None:
        def fn(x):
            return ops.conv_mvu(
                x, params.weights, kernel=conv["kernel"], stride=conv["stride"],
                pad=conv["pad"], mode=cfg.mode,
                k_bits=cfg.in_features if cfg.mode == "xnor" else None,
                thresholds=params.thresholds, out_scale=params.out_scale,
                backend=cand.backend, **blocks)
        return fn

    if cand.packed:
        # pack once outside the timed fn -- at run time the packed storage
        # is what lives in HBM (the pack_weights build rewrite).  Configs
        # already rewritten by that step carry packed weights as-is.
        from repro.kernels.mvu_packed import pack_mvu_weights

        w_packed = (params.weights if cfg.packed
                    else pack_mvu_weights(params.weights, cfg.mode))

        def fn(x):
            return ops.mvu(
                x, w_packed, cfg.mode, k_bits=cfg.in_features,
                thresholds=params.thresholds, out_scale=params.out_scale,
                backend=cand.backend, packed=True, **blocks)
        return fn

    def fn(x):
        return ops.mvu(
            x, params.weights, cfg.mode,
            k_bits=cfg.in_features if cfg.mode == "xnor" else None,
            thresholds=params.thresholds, out_scale=params.out_scale,
            backend=cand.backend, **blocks)
    return fn


def tune_node(
    node: Node,
    in_shape: tuple | None = None,
    *,
    vmem_bytes: int = VMEM_BYTES,
    sample_m: int = 256,
    reps: int = 3,
    max_measure: int = 8,
    margin: float = 0.05,
    timer=None,
    seed: int = 0,
    allow_packed: bool = True,
) -> dict:
    """Measure the pruned shortlist for one finalized mvu/conv_mvu node.

    Returns the winning cache entry.  Candidates whose output is not
    bit-exact with the heuristic schedule are discarded -- tuning must
    never trade correctness for speed -- and a challenger must beat the
    incumbent by ``margin`` (paired timing still jitters a few percent on
    shared hosts; a noise-driven "win" would churn the cache for nothing).
    """
    timer = timer if timer is not None else paired_timer
    cfg: MVUConfig = node.attrs["config"]
    params = node.params["mvu"]
    conv = None
    n_pixels = 1
    if node.op == "conv_mvu":
        conv = {k: node.attrs[k] for k in ("kernel", "stride", "pad")}
        out_shape = ir.propagate(node, in_shape)
        n_pixels = ir.n_pixels(out_shape)
    t = params.thresholds
    n_thresh = 0 if t is None else int(t.shape[-1])
    cands = enumerate_candidates(
        cfg, n_pixels=n_pixels, n_thresh=n_thresh, in_shape=in_shape,
        conv=conv, vmem_bytes=vmem_bytes, max_measure=max_measure)

    x = _synth_activations(cfg, sample_m, in_shape, conv, seed=seed)
    base_blocks = KernelBlocks.from_blocks(
        {**{"block_m": cfg.block_m}, **cfg.kernel_blocks()})
    base_cycles = cfg.resolved_folding().cycles(
        cfg.out_features, cfg.in_features, n_pixels)
    base = Candidate(cfg.backend, base_blocks, base_cycles, 0,
                     packed=cfg.packed or natively_packed(cfg, cfg.backend))
    base_fn = _node_fn(cfg, params, base, conv)
    want = np.asarray(base_fn(x))

    if conv is not None:
        oh = out_dim(in_shape[0], conv["kernel"], conv["stride"], conv["pad"])
        ow = out_dim(in_shape[1], conv["kernel"], conv["stride"], conv["pad"])

    def effective(c: Candidate) -> tuple:
        """What the kernel actually consumes -- candidates that differ only
        in ignored fields (conv ignores the K blocks, block_m acts through
        rows_per_tile) must not be timed against each other."""
        if conv is not None:
            rt, bn = fit_conv_blocks(
                oh, ow, cfg.out_features, block_m=c.blocks.block_m,
                block_n=c.blocks.block_n,
                rows_per_tile=c.blocks.rows_per_tile).values()
            return (c.backend, bn, rt)
        kw = legal_blocks(c.blocks.as_kwargs(cfg.mode, c.packed),
                          cfg.out_features, cfg.in_features, cfg.mode,
                          packed=c.packed)
        kw.pop("rows_per_tile", None)
        # packed xnor runs the same Pallas kernel but a different XLA path,
        # so the storage axis is part of the effective identity throughout
        return (c.backend, c.packed, tuple(sorted(kw.items())))

    best, best_speed = base, 1.0
    measured = 0
    seen_eff = {effective(base)}
    for cand in cands:
        if cfg.packed and not cand.packed:
            continue  # packed storage cannot feed the canonical kernels
        if cand.packed and not allow_packed and cfg.mode != "xnor":
            continue  # pack="never": storage rewrite is policy-excluded
        if effective(cand) in seen_eff:
            continue
        seen_eff.add(effective(cand))
        fn = _node_fn(cfg, params, cand, conv)
        if not np.array_equal(np.asarray(fn(x)), want):
            continue  # never accept a schedule that changes the numbers
        _, _, speedup = timer(base_fn, fn, x, reps=reps)
        measured += 1
        if speedup > best_speed * (1.0 + margin):
            best, best_speed = cand, speedup
    return best.entry(
        speedup=float(best_speed),
        measured_candidates=measured,
        epilogue=epilogue_form(params),
        n_pixels=int(n_pixels),
    )


def apply_entry(cfg: MVUConfig, entry: dict) -> MVUConfig:
    """Pin a cache entry's schedule onto an MVUConfig.

    An entry carrying ``"packed": true`` selects the bit-packed datapath;
    the weight storage itself is rewritten later by the ``pack_weights``
    build step (``repro.core.lowering.pack_weights``).
    """
    blocks = KernelBlocks.from_blocks(entry)
    return MVUConfig(**{
        **cfg.__dict__,
        "backend": entry.get("backend", cfg.backend),
        "packed": bool(entry.get("packed", cfg.packed)),
        "blocks": blocks,
        "block_m": blocks.block_m,
    })


def tune_graph(
    graph: Graph,
    *,
    cache: ScheduleCache | None = None,
    mode: str = "cache",
    device: str | None = None,
    timer=None,
    vmem_bytes: int = VMEM_BYTES,
    allow_packed: bool = True,
    **tune_kwargs,
) -> Graph:
    """Annotate every finalized mvu/conv_mvu node with its tuned schedule.

    ``mode="cache"`` is a pure lookup: hits rewrite the node's config,
    misses keep the heuristic schedule, nothing is ever measured.
    ``mode="auto"`` measures misses via :func:`tune_node` and fills the
    cache.  Returns a new graph (input nodes are shared, rewritten nodes
    are fresh ``Node`` objects) so the caller's graph keeps its heuristic
    configs.
    """
    if mode not in ("cache", "auto"):
        raise ValueError(f"tune mode must be 'cache' or 'auto', got {mode!r}")
    cache = cache if cache is not None else default_cache()
    out: Graph = ir.Graph()
    for node, ins, out_shape in ir.io_shapes(graph):
        if node.op not in ("mvu", "conv_mvu") or "mvu" not in node.params:
            out.append(node)
            continue
        in_shape = ins[0] if ins else None
        cfg: MVUConfig = node.attrs["config"]
        if cfg.packed and cfg.blocks is not None:
            # the node already carries a tuned packed schedule (a prior
            # pass ran apply_entry); looking it up again under the
            # ``|packed``-suffixed key would re-measure on every
            # downstream pass and duplicate the entry in the cache
            out.append(node)
            continue
        key = node_key(cfg, epilogue=epilogue_form(node.params["mvu"]),
                       n_pixels=ir.n_pixels(out_shape), device=device,
                       op=op_tag(node, in_shape))
        entry = cache.get(key)
        if (entry is not None and entry.get("packed")
                and not allow_packed and cfg.mode != "xnor"):
            # pack="never" policy: a cached packed-datapath winner would
            # need the storage rewrite the build config forbids, so the
            # node keeps its heuristic schedule (xnor storage is packed
            # words either way -- its entries apply under any policy)
            entry = None
        elif entry is None and mode == "auto":
            entry = tune_node(node, in_shape, timer=timer,
                              vmem_bytes=vmem_bytes,
                              allow_packed=allow_packed, **tune_kwargs)
            cache.put(key, entry)
        if entry is None:
            out.append(node)
            continue
        out.append(Node(node.op, node.name,
                        {**node.attrs, "config": apply_entry(cfg, entry)},
                        node.params, inputs=node.inputs))
    return out


# ------------------------------------------------------------ engine level
def synth_input(graph: Graph, batch: int, seed: int = 0):
    """Random integer activations matching the graph's input node."""
    import jax.numpy as jnp

    heads = [n for n in graph if n.op == "input"]
    if len(heads) != 1:
        raise ValueError(
            f"graph must have exactly one input node, found {len(heads)}")
    head = heads[0]
    shape = tuple(head.attrs["shape"])
    bits = head.attrs.get("bits", 1)
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**bits, (batch, *shape)), jnp.int32)


def tune_engine(
    graph: Graph,
    batch: int,
    *,
    cache: ScheduleCache,
    device: str | None = None,
    tiles: tuple[int, ...] | None = None,
    reps: int = 5,
    margin: float = 0.1,
    timer=None,
    seed: int = 0,
) -> dict:
    """Search the engine-level microbatch tile (FINN FIFO-depth analog).

    Builds cache-tuned engines over the candidate tiles, times each against
    the heuristic plan with the paired timer, and records the winner under
    :func:`engine_key`.  The per-node schedules must already be in
    ``cache`` (run :func:`tune_graph` in auto mode first).  Whole-engine
    timings jitter more than kernel timings, so a challenger tile must beat
    the incumbent by ``margin`` before it displaces the heuristic plan.
    """
    from repro.core.engine import FusedEngine

    timer = timer if timer is not None else paired_timer
    # the baseline (and every candidate) must run the node-tuned schedules
    # WITHOUT any engine-level entry: a previous tune_engine result in
    # ``cache`` would otherwise contaminate the heuristic plan and the
    # recorded speedup would silently become relative-to-last-tuning
    node_cache = ScheduleCache({k: v for k, v in cache.entries.items()
                                if not k.startswith("engine|")})
    base = FusedEngine(graph, tune="cache", cache=node_cache)
    heur_tile = base.plan(batch).microbatch
    if tiles is None:
        tiles = tuple(sorted({heur_tile, heur_tile * 2, heur_tile * 4,
                              heur_tile * 8, batch}))
    x = synth_input(graph, batch, seed=seed)
    want = np.asarray(base(x))

    best_tile, best_speed = heur_tile, 1.0
    for tile in tiles:
        if tile == heur_tile or tile < 1:
            continue
        cand = FusedEngine(graph, tune="cache", cache=node_cache)
        cand._tile = int(tile)
        if not np.array_equal(np.asarray(cand(x)), want):
            continue
        _, _, speedup = timer(base, cand, x, reps=reps)
        if speedup > best_speed * (1.0 + margin):
            best_tile, best_speed = int(tile), speedup
    entry = {"microbatch": int(best_tile), "speedup": float(best_speed),
             "batch": int(batch)}
    cache.put(engine_key(base.graph, device=device), entry)
    return entry
