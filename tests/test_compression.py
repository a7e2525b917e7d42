"""Huffman codec + quantization properties (hypothesis)."""
import numpy as np
import pytest
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


def _skewed(n, bits, skew, seed):
    """n symbols of a ``bits``-bit alphabet, peaked in the middle like
    quantized KV."""
    alpha = 1 << bits
    p = np.exp(-skew * np.abs(np.arange(alpha) - alpha / 2) / alpha)
    return np.random.default_rng(seed).choice(
        alpha, size=n, p=p / p.sum()).astype(np.uint16)


def _to_max_len(seed):
    """Counts halving from symbol to symbol: the Huffman code of the
    17 symbols reaches MAX_LEN (lengths 1, 2, ..., 16, 16)."""
    counts = [1 << (16 - i) for i in range(16)] + [1]
    x = np.repeat(np.arange(17, dtype=np.uint16), counts)
    return np.random.default_rng(seed).permutation(x)


# each case: planes decoded in one call, as (symbols, alphabet, streams)
DECODE_MANY_CASES = {
    "widths_3_5_8": lambda: [(_skewed(4096, 3, 1.0, 1), 8, 64),
                             (_skewed(4096, 5, 2.0, 2), 32, 64),
                             (_skewed(4096, 8, 4.0, 3), 256, 64)],
    "lengths_and_short_last_stream": lambda: [
        (_skewed(64 * 50 + 13, 5, 3.0, 4), 32, 64),
        (_skewed(1000, 5, 2.0, 5), 32, 64),
        (_skewed(70, 5, 1.0, 6), 32, 64),
        (_skewed(5, 5, 1.0, 7), 32, 64),
        (_skewed(20000, 5, 0.5, 8), 32, 7)],
    "one_symbol_and_empty": lambda: [
        (np.full(3000, 9, np.uint16), 32, 64),
        (np.zeros(0, np.uint16), 32, 64),
        (_skewed(3000, 4, 2.0, 9), 16, 64),
        (np.full(1, 3, np.uint16), 8, 64)],
    "codes_reach_max_len": lambda: [(_to_max_len(10), 256, 64),
                                    (_skewed(5000, 5, 2.0, 11), 32, 64)],
    "only_empty": lambda: [(np.zeros(0, np.uint16), 32, 64)] * 2,
    "one_plane_one_stream": lambda: [(_skewed(777, 6, 2.0, 12), 64, 1)],
}


@pytest.mark.parametrize("case", sorted(DECODE_MANY_CASES))
def test_huffman_decode_many_roundtrip(case):
    planes = DECODE_MANY_CASES[case]()
    encs = [H.encode(x, alpha, n_streams=s) for x, alpha, s in planes]
    if case == "codes_reach_max_len":
        assert int(encs[0].code.lengths.max()) == H.MAX_LEN
    decoded = H.decode_many(encs)
    assert len(decoded) == len(planes)
    for (x, _, _), d, enc in zip(planes, decoded, encs):
        assert d.dtype == np.uint16 and np.array_equal(d, x)
        assert np.array_equal(H.decode(enc), x)
    lanes, steps, bits = H.lockstep_shape(encs)
    assert lanes == sum(e.streams.shape[0] for e in encs)
    assert steps == max(int(e.n_per_stream.max()) for e in encs)
    assert bits == max(int(e.code.lengths.max()) for e in encs)


def test_huffman_decode_many_of_nothing():
    assert H.decode_many([]) == []
    assert H.lockstep_shape([]) == (0, 0, 0)


@pytest.mark.parametrize("bits", [3, 5, 8])
def test_huffman_cut_table_is_the_full_table_strided(bits):
    """A table indexed by b bits holds entry j of the full one at
    j << (MAX_LEN - b), for every b from the longest code to MAX_LEN."""
    code = H.HuffmanCode.from_counts(
        np.bincount(_skewed(5000, bits, 3.0, bits), minlength=1 << bits))
    sym16, len16 = code.decode_table()
    for b in range(int(code.lengths.max()), H.MAX_LEN + 1):
        sym, ln = code.decode_table(b)
        step = 1 << (H.MAX_LEN - b)
        assert np.array_equal(sym, sym16[::step])
        assert np.array_equal(ln, len16[::step])


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
