"""Huffman codec + quantization properties (hypothesis)."""
import numpy as np
import pytest  # noqa: F401
# real hypothesis in CI; deterministic stub from tests/_vendor otherwise
# (wired by conftest.py) — the suite never skips
from hypothesis import given, settings, strategies as st

from repro.compression import huffman as H
from repro.compression.allocate import (SCHEDULES, allocate_bits,
                                        chunk_saliency, ladder_shift,
                                        saliency_ranks, schedule_of)
from repro.compression.quantize import (BITRATE_LEVELS, dequantize,
                                        layerwise_bits, quant_error,
                                        quantize, snap_to_ladder)


@settings(max_examples=25, deadline=None, derandomize=True)
@given(st.integers(1, 20000), st.integers(2, 6), st.integers(1, 64),
       st.floats(0.2, 6.0))
def test_huffman_roundtrip(n, bits, streams, skew):
    rng = np.random.default_rng(n * 7 + bits)
    alpha = 1 << bits
    # skewed multinomial like quantized KV
    p = np.exp(-skew * np.abs(np.arange(alpha) - alpha / 2) / alpha)
    p /= p.sum()
    x = rng.choice(alpha, size=n, p=p).astype(np.uint16)
    enc = H.encode(x, alpha, n_streams=streams)
    dec = H.decode(enc)
    assert np.array_equal(dec, x)


def test_huffman_near_entropy(rng):
    x = np.clip(rng.normal(16, 3, 200_000), 0, 31).astype(np.uint16)
    enc = H.encode(x, 32, n_streams=256)
    ent = H.entropy_bits(x, 32)
    actual = enc.payload_bytes() * 8 / len(x)
    # within 8% of the entropy bound at this scale
    assert actual < ent * 1.08 + 0.1


def test_huffman_constant_sequence():
    x = np.full(5000, 7, np.uint16)
    enc = H.encode(x, 32, n_streams=16)
    assert np.array_equal(H.decode(enc), x)
    assert enc.payload_bytes() * 8 / len(x) < 1.5  # ~1 bit/sym + overhead


def test_huffman_empty():
    enc = H.encode(np.zeros(0, np.uint16), 32)
    assert len(H.decode(enc)) == 0


@settings(max_examples=20, deadline=None, derandomize=True)
@given(st.integers(2, 8), st.sampled_from([16, 32, 64, 128]))
def test_quantize_error_bound(bits, group):
    rng = np.random.default_rng(bits * group)
    x = rng.normal(size=(64, 64)).astype(np.float32)
    qt = quantize(x, bits, group)
    from repro.compression.quantize import dequantize
    xr = dequantize(qt)
    # max error <= half step of the worst group
    assert np.abs(xr - x).max() <= qt.scales.max() / 2 + 1e-6
    # monotone: more bits -> lower error
    if bits < 8:
        assert quant_error(x, bits + 1, group) <= \
            quant_error(x, bits, group) + 1e-9


def test_layerwise_bits_ladder():
    for lvl in range(len(BITRATE_LEVELS)):
        for layer in (0, 10, 30):
            bk = layerwise_bits(lvl, layer, 32, is_key=True)
            bv = layerwise_bits(lvl, layer, 32, is_key=False)
            assert 2 <= bv <= bk <= 8  # keys get >= bits than values


def test_layerwise_bits_on_ladder_grid():
    """Regression: layerwise_bits used to emit off-ladder widths (7 from
    level 1 + key bonus -> KeyError in QUALITY_OF_BITS; 2 below the
    memory server's 3-bit floor). Every (level, layer, is_key) cell of
    the grid must now be a BITRATE_LEVELS width, keys still >= values."""
    for lvl in range(len(BITRATE_LEVELS)):
        for n_layers in (16, 32, 48):
            for layer in range(n_layers):
                bk = layerwise_bits(lvl, layer, n_layers, is_key=True)
                bv = layerwise_bits(lvl, layer, n_layers, is_key=False)
                assert bk in BITRATE_LEVELS, (lvl, layer, n_layers, bk)
                assert bv in BITRATE_LEVELS, (lvl, layer, n_layers, bv)
                assert bv <= bk


def test_snap_to_ladder():
    assert [snap_to_ladder(b) for b in range(2, 9)] == \
        [3, 3, 4, 5, 6, 8, 8]  # nearest rung, ties break finer
    # monotone: never reorders two widths
    snapped = [snap_to_ladder(b) for b in range(2, 9)]
    assert snapped == sorted(snapped)


def test_quantize_tail_group_regression(rng):
    """Regression: quantize() zero-padded BEFORE per-group min/max, so a
    non-divisible all-positive tensor's tail group got lo pulled to 0.0
    and a widened step. Edge-padding keeps the tail group's affine
    params on its real values: the non-divisible round-trip error must
    stay within the divisible-length bound."""
    for n, group in [(97, 32), (1000, 64), (33, 32), (130, 128)]:
        x = rng.uniform(5.0, 6.0, n).astype(np.float32)
        qt = quantize(x, 4, group)
        err = np.abs(dequantize(qt) - x).max()
        # divisible-length reference on the same distribution
        xd = rng.uniform(5.0, 6.0, (n // group + 1) * group)
        xd = xd.astype(np.float32)
        err_div = np.abs(dequantize(quantize(xd, 4, group)) - xd).max()
        # pre-fix the tail error was ~5x the step (lo dragged to 0.0)
        assert err <= err_div * 1.25 + 1e-6, (n, group, err, err_div)
        # and the universal half-step bound still holds
        assert err <= qt.scales.max() / 2 + 1e-6


def test_quantize_spans_field(rng):
    """The per-group affine parameters the dequant kernel reads: scales
    are the group's value span (hi - lo) / (2^bits - 1) in fp32, computed
    on the host, and zeros the group's minimum."""
    for bits in (3, 5, 8):
        x = rng.normal(size=500).astype(np.float32)
        qt = quantize(x, bits, 64)
        g = np.pad(x, (0, (-x.size) % 64), mode="edge").reshape(-1, 64)
        span = np.maximum(g.max(1) - g.min(1), np.float32(1e-8))
        assert qt.scales.dtype == np.float32
        assert np.array_equal(qt.scales, span / np.float32((1 << bits) - 1))
        assert np.array_equal(qt.zeros, g.min(1))


def test_allocation_schedules(rng):
    act = rng.uniform(1.0, 20.0, (8, 4, 2))
    ent = rng.uniform(0.5, 4.0, (4, 2))
    for name in ("uniform", "flat"):
        out = allocate_bits(act, ent, 5, schedule_of(name))
        assert (out == 5).all()  # empty-rule schedules: base everywhere
    out = allocate_bits(act, ent, 5, schedule_of("attention"))
    assert out.shape == act.shape
    assert set(np.unique(out)) <= set(BITRATE_LEVELS)
    # hot band finer, cold band coarser, and both non-empty
    assert (out == 6).any() and (out == 4).any()
    # saliency order respected: every 6-bit chunk outranks every 4-bit
    sal = chunk_saliency(act, ent)
    assert sal[out == 6].min() >= sal[out == 4].max()
    # off-ladder base snaps before shifting
    out7 = allocate_bits(act, ent, 7, schedule_of("flat"))
    assert (out7 == 8).all()


def test_allocation_ranks_and_shift():
    r = saliency_ranks(np.array([3.0, 1.0, 2.0, 2.0]))
    assert np.array_equal(r, [0.75, 0.0, 0.25, 0.5])  # stable ties
    assert ladder_shift(5, +1) == 6 and ladder_shift(5, -1) == 4
    assert ladder_shift(8, +2) == 8 and ladder_shift(3, -2) == 3  # clamp
    assert ladder_shift(7, 0) == 8  # snapped first


def test_allocation_entropy_tilt():
    """With equal attention mass, higher-entropy layers get the finer
    rungs; zero entropy degenerates to pure attention ranking."""
    act = np.ones((6, 2, 1))
    ent = np.array([[4.0], [0.5]])
    out = allocate_bits(act, ent, 5, SCHEDULES["attention"])
    assert out[:, 0, :].min() >= out[:, 1, :].max()
    out0 = allocate_bits(act, np.zeros((2, 1)), 5, SCHEDULES["attention"])
    assert set(np.unique(out0)) <= set(BITRATE_LEVELS)


def test_schedule_of_unknown():
    with pytest.raises(KeyError):
        schedule_of("nope")
