"""FINN's folding pass, ported to TPU tile selection.

FINN time-multiplexes the weight matrix (N = O_c rows, K = Kd^2*I_c cols)
onto a PE x SIMD array:

    neuron fold   NF = N / PE        (PE must divide N)
    synapse fold  SF = K / SIMD      (SIMD must divide K)
    cycles per output pixel = NF * SF   at II = 1
    total cycles = n_pixels * NF * SF

On TPU, PE maps to the kernel's block_n and SIMD to block_k (x32 synapses
per packed word for the XNOR datapath), rounded to the chip's layout, so
"folding" becomes BlockSpec tile selection under a VMEM budget.  The cycle
model (``Folding.cycles``) and the balance condition stay on the FINN
(PE, SIMD) values; only the tiles the kernels run are rounded.

The pipeline balancer reproduces FINN's *Folding and Resource Estimation*
pass: given a cycle target, assign each layer the smallest PE*SIMD product
that meets it, which rate-matches the streaming pipeline (the slowest layer
sets the initiation interval of the whole dataflow graph).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

from repro.kernels import mvu_int, mvu_packed, mvu_xnor
from repro.kernels.packing import WORD_BITS, num_words


@dataclasses.dataclass(frozen=True)
class Folding:
    pe: int
    simd: int

    def cycles(self, n: int, k: int, n_pixels: int = 1) -> int:
        nf = -(-n // self.pe)
        sf = -(-k // self.simd)
        return n_pixels * nf * sf

    def conv_cycles(self, n: int, k: int, oh: int, ow: int) -> int:
        """Paper Eq. 1 over the pixel dimension: the SWU feeds one K-window
        per output pixel, so a conv layer costs OH*OW * NF * SF cycles."""
        return self.cycles(n, k, n_pixels=oh * ow)

    def validate(self, n: int, k: int) -> None:
        if n % self.pe:
            raise ValueError(f"PE={self.pe} must divide N={n}")
        if k % self.simd:
            raise ValueError(f"SIMD={self.simd} must divide K={k}")


def divisors(x: int) -> list[int]:
    out = [d for d in range(1, int(math.isqrt(x)) + 1) if x % d == 0]
    return sorted(set(out + [x // d for d in out]))


def weight_mem_depth(n: int, k: int, fold: Folding) -> int:
    """Paper Eq. (2): D_mem = K*N / (SIMD*PE), per-PE weight memory depth."""
    return (k * n) // (fold.simd * fold.pe)


def input_buffer_depth(k: int, fold: Folding) -> int:
    """Input buffer depth K/SIMD (reused across the NF row groups)."""
    return -(-k // fold.simd)


def choose_folding(
    n: int,
    k: int,
    *,
    target_cycles: int | None = None,
    max_pe: int = 128,
    max_simd: int = 128,
    n_pixels: int = 1,
) -> Folding:
    """Smallest PE*SIMD meeting ``target_cycles`` (FINN folding objective).

    With no target, returns the largest legal array (fully-parallel bound).
    Ties break toward larger SIMD (deeper dot products amortize the
    accumulator, mirroring FINN's preference for SIMD before PE).
    """
    pes = [d for d in divisors(n) if d <= max_pe]
    simds = [d for d in divisors(k) if d <= max_simd]
    if target_cycles is None:
        return Folding(max(pes), max(simds))
    best: Folding | None = None
    best_cost = None
    for pe in pes:
        for simd in simds:
            f = Folding(pe, simd)
            if f.cycles(n, k, n_pixels) <= target_cycles:
                cost = (pe * simd, -simd)
                if best_cost is None or cost < best_cost:
                    best, best_cost = f, cost
    if best is None:
        best = Folding(max(pes), max(simds))  # can't meet target: go maximal
    return best


def balance_pipeline(
    layer_shapes: Sequence[tuple[int, int, int]],  # (N, K, n_pixels)
    *,
    slowest_cycles: int | None = None,
    max_pe: int = 128,
    max_simd: int = 128,
) -> list[Folding]:
    """Rate-match a chain of MVU layers (FINN balanced-pipeline condition).

    Every layer gets the cheapest folding whose cycle count does not exceed
    the pipeline target; the default target is the cycle count of the
    heaviest layer at full parallelism (nothing can beat that anyway).
    """
    if slowest_cycles is None:
        slowest_cycles = max(
            Folding(min(max_pe, n), min(max_simd, k)).cycles(n, k, px)
            for n, k, px in layer_shapes
        )
    return [
        choose_folding(n, k, target_cycles=slowest_cycles,
                       max_pe=max_pe, max_simd=max_simd, n_pixels=px)
        for n, k, px in layer_shapes
    ]


def steps_in_words(mode: str, packed: bool = False) -> bool:
    """Whether the datapath steps K in 32-bit packed words (block_kw)."""
    return mode == "xnor" or (packed and mode == "binary")


def legal_blocks(blocks: dict, n: int, k: int, mode: str, *,
                 packed: bool = False) -> dict[str, int]:
    """The tile the kernels run for a requested (N, K) schedule.

    Asks the kernel that runs ``mode`` for its own rounding (each kernel
    module's ``fit_blocks``): block_n to 128 lanes or the whole N, the K
    step to 128 lanes (of int8 synapses, packed words or 2-bit lane bytes)
    or the whole K, block_m to 8 sublanes.  Keys other than the block
    edges pass through unchanged.
    """
    if steps_in_words(mode, packed):
        fit, kkey = mvu_xnor.fit_blocks, "block_kw"
    elif packed:
        fit, kkey = mvu_packed.fit_int2_blocks, "block_k"
    else:
        fit, kkey = mvu_int.fit_blocks, "block_k"
    return {**blocks, **fit(n, k, block_m=blocks["block_m"],
                            block_n=blocks["block_n"], **{kkey: blocks[kkey]})}


def to_tpu_blocks(fold: Folding, mode: str, m: int = 128, *, n: int, k: int,
                  packed: bool = False) -> dict[str, int]:
    """Map (PE, SIMD) onto the Pallas block shapes the kernels run.

    block_n = PE (output rows in parallel), block_k = SIMD synapses per grid
    step; the XNOR datapath packs 32 synapses per word so block_kw =
    SIMD / 32, and the packed binary datapath steps the same word axis.
    Each edge is then rounded to the chip's layout by
    :func:`legal_blocks`: a multiple of 128 lanes (8 sublanes for
    block_m), or the whole (N, K) dim.  Small FPGA-style arrays therefore
    run as one full-width tile; the cycle model keeps the FINN folding.
    """
    if steps_in_words(mode, packed):
        want = {"block_m": m, "block_n": fold.pe,
                "block_kw": -(-fold.simd // WORD_BITS)}
    else:
        want = {"block_m": m, "block_n": fold.pe, "block_k": fold.simd}
    return legal_blocks(want, n, k, mode, packed=packed)


def block_candidates(
    n: int,
    k: int,
    mode: str,
    *,
    block_ms: Sequence[int] = (32, 128, 256),
    max_block: int = 512,
    packed: bool = False,
) -> list[dict[str, int]]:
    """Enumerate the legal Pallas tile schedules for an (N, K) layer.

    The candidate axes come from the layer's folding divisors plus the
    full-MXU defaults, each rounded to the chip's layout by
    :func:`legal_blocks` exactly like :func:`to_tpu_blocks` -- so the
    heuristic schedule is always in the set, the autotuner can only match
    or beat it, and it never times a tile the chip's compiler refuses.
    ``block_kw`` (xnor and the packed binary datapath) ranges over
    divisors of the packed word count.  Candidates are unique dicts;
    ordering/pruning is the caller's job (``repro.core.autotune``).
    """
    bns = [b for b in divisors(n) + [128] if b <= max(max_block, 8)]
    if steps_in_words(mode, packed):
        kkey, ks = "block_kw", divisors(num_words(k)) + [8]
    else:
        kkey = "block_k"
        ks = [b for b in divisors(k) + [128, min(512, k)]
              if b <= max(max_block, 8)]
    out: list[dict[str, int]] = []
    for bm in block_ms:
        for bn in bns:
            for bk in ks:
                c = legal_blocks({"block_m": bm, "block_n": bn, kkey: bk},
                                 n, k, mode, packed=packed)
                if c not in out:
                    out.append(c)
    return out
