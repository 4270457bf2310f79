"""Analytical resource model -- the TPU analog of the paper's LUT/FF/BRAM
counts (Section 6.2) and cycle/critical-path analysis (6.3).

The RTL implementation's virtue in the paper is that its costs are
*predictable by construction* (explicit cycle-accurate schedule), while the
HLS side must be measured after compilation.  We keep that split:

  * this module = the predictable, closed-form model for the hand-scheduled
    Pallas kernel (the "RTL" side);
  * ``compiled.memory_analysis()/cost_analysis()`` on the XLA-compiled
    reference = the measured "HLS" side (see benchmarks/resource_sweep.py).

Metric mapping (DESIGN.md section 2):
    LUT analog   -> VMEM working-set bytes of one grid step (compute fabric)
    FF analog    -> persistent pipeline state (accumulators + control)
    BRAM analog  -> buffered memories: weight store + input buffer bytes
    critical path-> per-grid-step work (MACs) / array peak
    exec cycles  -> folding cycle model (II = 1)
"""

from __future__ import annotations

import dataclasses

from repro.core.folding import (
    Folding,
    input_buffer_depth,
    legal_blocks,
    steps_in_words,
    to_tpu_blocks,
    weight_mem_depth,
)
from repro.kernels._common import VMEM_LIMIT_BYTES
from repro.kernels.packing import WORD_BITS, num_int2_bytes, num_words

# TPU v5e hardware constants (roofline terms use the same numbers).
PEAK_BF16_FLOPS = 197e12
PEAK_INT8_OPS = 394e12
HBM_BW = 819e9
ICI_BW_PER_LINK = 50e9
VMEM_BYTES = VMEM_LIMIT_BYTES  # the kernels' scoped VMEM limit
CLOCK_HZ = 940e6  # v5e core clock, for cycle -> ns conversions


def _act_bytes(mode: str, bits: int) -> float:
    if mode == "xnor":
        return 1.0 / 8.0
    return 1.0  # int4 carried in int8 on the MXU path


@dataclasses.dataclass(frozen=True)
class MVUResources:
    lut_bytes: int  # VMEM working set per grid step
    ff_bytes: int  # persistent accumulator/control state
    bram_bytes: int  # weight memory + input buffer
    weight_mem_depth: int
    input_buffer_depth: int
    cycles: int
    macs: int
    ns_per_inference: float
    weight_bytes: int = 0  # HBM-resident weight bytes as stored
    canonical_weight_bytes: int = 0  # same weights without packing

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def weight_resident_bytes(n: int, k: int, mode: str, packed: bool) -> int:
    """HBM-resident bytes of one (N, K) weight matrix as actually stored.

    Canonical storage is int8 rows for binary/standard; the xnor coding is
    always bit-packed (its canonical form IS uint32 words).  Packed binary
    stores uint32 bitplanes (8x smaller than int8 rows); packed standard
    stores 4x 2-bit lanes per byte.
    """
    if steps_in_words(mode, packed):
        return n * num_words(k) * 4
    if packed:
        return n * num_int2_bytes(k)
    return n * k  # canonical int8 rows


def mvu_resources(
    n: int,
    k: int,
    fold: Folding,
    *,
    mode: str = "standard",
    weight_bits: int = 4,
    act_bits: int = 4,
    n_pixels: int = 1,
    block_m: int = 128,
    n_thresh: int = 0,
    blocks: dict | None = None,
    packed: bool = False,
) -> MVUResources:
    """Closed-form resource estimate for one MVU layer instance.

    The VMEM working set (``lut_bytes``) is computed from the *actual*
    kernel blocks, not the raw folding: ``folding.legal_blocks`` rounds
    ``block_n`` and the K step to the chip's layout, and the kernel streams
    one (block_m, K-step) input tile per grid step.  Pass ``blocks`` to
    estimate an explicit (e.g. autotuned) schedule; otherwise the folding's
    derived blocks are used.  ``packed`` models the bit-packed datapath:
    the weight tile (and the HBM-resident ``weight_bytes``) shrink by the
    packing factor, and the in-VMEM unpack of one weight tile is counted.
    BRAM/cycle terms stay on the folding abstraction (paper Eq. 1/2).
    """
    wb = weight_bits / 8.0
    ab = _act_bytes(mode, act_bits)
    if blocks is None:
        blocks = to_tpu_blocks(fold, mode, block_m, n=n, k=k, packed=packed)
    blocks = legal_blocks(blocks, n, k, mode, packed=packed)
    block_m = blocks["block_m"]
    bn = blocks["block_n"]

    if mode == "xnor":
        # packed-word datapath: operands live as uint32 words in VMEM, and
        # the (bm, bn, bkw) xnor/popcount intermediate is the largest term
        bkw = blocks["block_kw"]
        a_tile = block_m * bkw * 4
        w_tile = bn * bkw * 4
        datapath = block_m * bn * bkw * 4
    elif packed and mode == "binary":
        # bitplane weights stepped in words, unpacked to (bn, bkw*32) int8
        bkw = blocks["block_kw"]
        a_tile = block_m * bkw * WORD_BITS
        w_tile = bn * bkw * 4
        datapath = bn * bkw * WORD_BITS * (4 + 1)
    else:
        # int8 operands on the MXU path regardless of logical weight_bits
        bk = blocks["block_k"]
        a_tile = block_m * bk
        # packed standard: the weight tile is 2-bit lanes, 4 per byte,
        # sign-extended through int32 into an int8 (bn, bk) tile
        w_tile = bn * (bk // 4 if packed else bk)
        datapath = bn * bk * (4 + 1) if packed else 0
    acc = block_m * bn * 4  # int32 PE accumulators
    thr = bn * n_thresh * 4
    out_tile = block_m * bn * 4

    lut = int(a_tile + w_tile + datapath + acc + out_tile + thr)
    ff = int(acc + 64)  # accumulators + FSM/counter state
    weight_store = int(n * k * wb)
    in_buf = int(k * ab)
    bram = weight_store + in_buf

    cycles = fold.cycles(n, k, n_pixels)
    macs = n * k * n_pixels
    ns = cycles / CLOCK_HZ * 1e9
    return MVUResources(
        lut_bytes=lut,
        ff_bytes=ff,
        bram_bytes=bram,
        weight_mem_depth=weight_mem_depth(n, k, fold),
        input_buffer_depth=input_buffer_depth(k, fold),
        cycles=cycles,
        macs=macs,
        ns_per_inference=ns,
        weight_bytes=weight_resident_bytes(n, k, mode, packed),
        canonical_weight_bytes=weight_resident_bytes(n, k, mode, False),
    )


# ---------------------------------------------------------- calibration
def fit_cycle_time(cycles, seconds) -> float:
    """Least-squares seconds-per-cycle over paired (cycles, measured s).

    The analytic model predicts *cycles*; turning them into wall-clock
    needs a realized cycle time.  Fitting one scalar across a whole sweep
    (every node of every design point) is the calibration the paper does
    implicitly when it reads its RTL cycle counts against a known clock:
    ``argmin_s sum_i (c_i * s - m_i)^2  =  sum(c*m) / sum(c^2)``.
    """
    c = [float(v) for v in cycles]
    m = [float(v) for v in seconds]
    if len(c) != len(m) or not c:
        raise ValueError("fit_cycle_time needs equal, non-empty sequences")
    denom = sum(v * v for v in c)
    if denom <= 0:
        raise ValueError("fit_cycle_time needs at least one non-zero cycle count")
    return sum(cv * mv for cv, mv in zip(c, m)) / denom


def cycle_model_errors(cycles, seconds, s_per_cycle: float | None = None
                       ) -> list[float]:
    """Signed relative error of the calibrated cycle model per sample:
    ``(predicted - measured) / measured`` with ``predicted = c * s``."""
    if s_per_cycle is None:
        s_per_cycle = fit_cycle_time(cycles, seconds)
    out = []
    for c, m in zip(cycles, seconds):
        m = float(m)
        if m <= 0:
            raise ValueError("measured seconds must be positive")
        out.append((float(c) * s_per_cycle - m) / m)
    return out


def error_summary(errors) -> dict:
    """Distribution summary of signed relative errors (JSON-safe).

    ``p50/p90/max`` are over |error| -- the calibration claim the CI gate
    holds (``model_error_p90`` in the explore artifact) is "the calibrated
    model lands within X% of the measurement for 90% of (node, design)
    pairs", not a statement about bias direction.
    """
    errs = [float(e) for e in errors]
    if not errs:
        return {"n": 0}
    mags = sorted(abs(e) for e in errs)

    def pct(q: float) -> float:
        idx = min(len(mags) - 1, max(0, int(round(q * (len(mags) - 1)))))
        return mags[idx]

    return {
        "n": len(errs),
        "mean_abs": sum(mags) / len(mags),
        "p50_abs": pct(0.50),
        "p90_abs": pct(0.90),
        "max_abs": mags[-1],
        "mean_signed": sum(errs) / len(errs),
    }


def roofline_terms(
    hlo_flops: float,
    hlo_bytes: float,
    collective_bytes: float,
    *,
    chips: int,
    peak_flops: float = PEAK_BF16_FLOPS,
    hbm_bw: float = HBM_BW,
    link_bw: float = ICI_BW_PER_LINK,
) -> dict:
    """The three roofline terms (seconds) + dominant bottleneck."""
    compute_s = hlo_flops / (chips * peak_flops)
    memory_s = hlo_bytes / (chips * hbm_bw)
    collective_s = collective_bytes / (chips * link_bw)
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    total = sum(terms.values())
    return {
        **terms,
        "dominant": dominant.removesuffix("_s"),
        "bound_s": bound,
        "roofline_fraction": (bound / total) if total > 0 else 0.0,
    }
