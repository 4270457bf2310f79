import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import folding as f


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 512), st.integers(1, 2048), st.integers(1, 64))
def test_cycle_model_conservation(n, k, pixels):
    """cycles * PE * SIMD == MACs when folds divide exactly (II=1 invariant)."""
    fold = f.choose_folding(n, k)
    fold.validate(n, k)
    cycles = fold.cycles(n, k, pixels)
    assert cycles * fold.pe * fold.simd == n * k * pixels


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 512), st.integers(1, 2048))
def test_choose_folding_meets_target(n, k):
    full = f.Folding(min(128, max(d for d in f.divisors(n) if d <= 128)),
                     min(128, max(d for d in f.divisors(k) if d <= 128)))
    target = full.cycles(n, k) * 4
    fold = f.choose_folding(n, k, target_cycles=target)
    fold.validate(n, k)
    assert fold.cycles(n, k) <= max(target, full.cycles(n, k))


def test_weight_mem_depth_eq2():
    # paper Eq. 2 with Kd=4, Ic=64, Oc=64, SIMD=32, PE=32
    k = 4 * 4 * 64
    n = 64
    fold = f.Folding(32, 32)
    assert f.weight_mem_depth(n, k, fold) == (k * n) // (32 * 32)
    assert f.input_buffer_depth(k, fold) == k // 32


def test_balance_pipeline_rate_matches():
    # NID MLP shapes (Table 6): (OFM, K, pixels)
    layers = [(64, 600, 1), (64, 64, 1), (64, 64, 1), (1, 64, 1)]
    folds = f.balance_pipeline(layers, max_pe=64, max_simd=64)
    cycles = [fd.cycles(n, k, px) for fd, (n, k, px) in zip(folds, layers)]
    slowest = max(cycles)
    # every stage is within the bottleneck's interval (balanced pipeline)
    assert all(c <= slowest for c in cycles)
    # and the bottleneck cannot be improved with legal folds under the caps
    full = [
        f.Folding(max(d for d in f.divisors(n) if d <= 64),
                  max(d for d in f.divisors(k) if d <= 64)).cycles(n, k, px)
        for n, k, px in layers
    ]
    assert slowest == max(full)


def test_illegal_folding_raises():
    with pytest.raises(ValueError):
        f.Folding(3, 2).validate(64, 64)
    with pytest.raises(ValueError):
        f.Folding(2, 7).validate(64, 64)


def test_to_tpu_blocks_xnor_words():
    # 64 synapses = 2 packed words; K = 64 bits is 2 words, so the word
    # step spans the whole (8-padded) packed dim
    blocks = f.to_tpu_blocks(f.Folding(64, 64), "xnor", n=64, k=64)
    assert blocks["block_kw"] == 8 and blocks["block_n"] == 64
    # at K = 8192 (256 words) the 2-word step rounds up to 128 lanes
    blocks = f.to_tpu_blocks(f.Folding(64, 64), "xnor", n=64, k=8192)
    assert blocks["block_kw"] == 128
    blocks = f.to_tpu_blocks(f.Folding(64, 64), "standard", n=64, k=64)
    assert blocks["block_k"] == 64 and blocks["block_n"] == 64


def test_block_candidates_contains_heuristic_and_clamps():
    n, k = 24, 96
    cands = f.block_candidates(n, k, "standard")
    heur = f.to_tpu_blocks(f.choose_folding(n, k), "standard", n=n, k=k)
    assert heur in cands
    assert all(c["block_n"] >= 8 and c["block_k"] >= 8 for c in cands)
    xc = f.block_candidates(24, 96, "xnor")
    assert all("block_kw" in c and c["block_kw"] >= 1 for c in xc)


@pytest.mark.parametrize("n,k,mode,packed", [
    (64, 600, "standard", False), (1, 64, "standard", False),
    (512, 2048, "binary", False), (512, 4608, "xnor", False),
    (10, 512, "xnor", False), (64, 600, "binary", True),
    (64, 600, "standard", True),
])
def test_block_candidates_are_chip_legal_and_unique(n, k, mode, packed):
    """Every candidate is the tile the kernels run (a fixed point of
    legal_blocks): block_n a multiple of 128 or the whole 8-padded N, the
    K step a multiple of 128 lanes or the whole K; no tile twice."""
    cands = f.block_candidates(n, k, mode, packed=packed)
    assert cands
    assert all(f.legal_blocks(c, n, k, mode, packed=packed) == c for c in cands)
    assert len({tuple(sorted(c.items())) for c in cands}) == len(cands)
    full_n = -(-n // 8) * 8
    for c in cands:
        assert c["block_n"] % 128 == 0 or c["block_n"] == full_n
        assert c["block_m"] % 8 == 0


def test_folding_cycles_unchanged_by_tile_rounding():
    """Table 6/7: the cycle model stays on the FINN (PE, SIMD) values even
    though the kernels round PE=64 x SIMD=50 up to a 128-lane K step."""
    from repro.configs import nid_mlp

    cycles = [f.Folding(pe, simd).cycles(n, k)
              for k, n, pe, simd in nid_mlp.LAYERS]
    assert cycles == [12, 8, 8, 8]
    blocks = f.to_tpu_blocks(f.Folding(64, 50), "standard", n=64, k=600)
    assert blocks == {"block_m": 128, "block_n": 64, "block_k": 128}
