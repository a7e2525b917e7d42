"""Pallas kernel validation (interpret=True) against pure-jnp oracles:
shape/dtype sweeps + hypothesis-driven randomized cases."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
# real hypothesis in CI; deterministic stub from tests/_vendor otherwise
# (wired by conftest.py) — the suite never skips
from hypothesis import given, settings, strategies as st

from repro.compression.quantize import dequantize, quantize
from repro.kernels.block_sparse_attn.kernel import block_sparse_attention
from repro.kernels.block_sparse_attn.ref import block_sparse_attention_ref
from repro.kernels.decode_attn.kernel import decode_attention
from repro.kernels.decode_attn.ref import decode_attention_ref
from repro.kernels.kv_dequant.kernel import kv_dequant
from repro.kernels.kv_dequant.ref import kv_dequant_ref
from repro.kernels.kv_dequant.ops import dequantize_chunk, dequantize_chunks
from repro.sparse.mask import block_scores, select_blocks

KEYS = jax.random.split(jax.random.PRNGKey(7), 8)


def _mask_for(q, k, mass, qb, kb, causal=True):
    sc = block_scores(q, k, q_block=qb, kv_block=kb, causal=causal)
    return select_blocks(sc, mass=mass, q_block=qb, kv_block=kb)


@pytest.mark.parametrize("bh,s,d,dtype", [
    (4, 512, 64, jnp.float32),
    (2, 1024, 128, jnp.float32),
    (2, 256, 128, jnp.bfloat16),
    (6, 384, 64, jnp.float32),
])
def test_block_sparse_attention_vs_ref(bh, s, d, dtype):
    q = jax.random.normal(KEYS[0], (bh, s, d), dtype)
    k = jax.random.normal(KEYS[1], (bh, s, d), dtype)
    v = jax.random.normal(KEYS[2], (bh, s, d), dtype)
    qb = kb = 128
    idx, cnt = _mask_for(q, k, 0.9, qb, kb)
    out = block_sparse_attention(q, k, v, idx, cnt, causal=True,
                                 q_block=qb, kv_block=kb, interpret=True)
    ref = block_sparse_attention_ref(q, k, v, idx, cnt, causal=True,
                                     q_block=qb, kv_block=kb)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("g", [2, 4, 8])
def test_block_sparse_attention_gqa(g):
    bh_kv, s, d = 2, 256, 64
    q = jax.random.normal(KEYS[3], (bh_kv * g, s, d), jnp.float32)
    k = jax.random.normal(KEYS[4], (bh_kv, s, d), jnp.float32)
    v = jax.random.normal(KEYS[5], (bh_kv, s, d), jnp.float32)
    kr = jnp.repeat(k, g, axis=0)
    vr = jnp.repeat(v, g, axis=0)
    idx, cnt = _mask_for(q, kr, 0.95, 128, 128)
    out = block_sparse_attention(q, k, v, idx, cnt, causal=True,
                                 kv_group=g, interpret=True)
    ref = block_sparse_attention_ref(q, kr, vr, idx, cnt, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_block_sparse_full_mask_equals_dense():
    """With every block active, the kernel reduces to causal attention."""
    bh, s, d = 2, 256, 64
    q = jax.random.normal(KEYS[0], (bh, s, d), jnp.float32)
    k = jax.random.normal(KEYS[1], (bh, s, d), jnp.float32)
    v = jax.random.normal(KEYS[2], (bh, s, d), jnp.float32)
    n_b = s // 128
    idx = jnp.broadcast_to(jnp.arange(n_b), (bh, n_b, n_b)).astype(jnp.int32)
    cnt = jnp.broadcast_to(jnp.arange(1, n_b + 1), (bh, n_b)).astype(jnp.int32)
    out = block_sparse_attention(q, k, v, idx, cnt, causal=True,
                                 interpret=True)
    # dense causal oracle
    sc = jnp.einsum("bqd,bkd->bqk", q, k) * d ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask, sc, -jnp.inf)
    ref = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(sc, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("b,hq,hkv,skv,d,klen,blk", [
    (2, 8, 2, 512, 64, 400, 256),
    (1, 4, 4, 1024, 128, 1024, 256),
    (3, 16, 2, 768, 128, 700, 128),
    (2, 8, 1, 512, 256, 333, 512),
])
def test_decode_attention_vs_ref(b, hq, hkv, skv, d, klen, blk):
    q = jax.random.normal(KEYS[0], (b, hq, d), jnp.float32)
    k = jax.random.normal(KEYS[1], (b, skv, hkv, d), jnp.float32)
    v = jax.random.normal(KEYS[2], (b, skv, hkv, d), jnp.float32)
    out = decode_attention(q, k, v, klen, kv_block=blk, interpret=True)
    ref = decode_attention_ref(q, k, v, klen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _group_rows(n, width, group):
    """(n, width) row blocks of width // group groups each, laid out as
    the kernel takes them: one group per row."""
    return n * (width // group), group


@pytest.mark.parametrize("n,width,group,bits", [
    (64, 128, 64, 5), (100, 512, 64, 4), (7, 256, 128, 8), (1024, 128, 32, 3),
])
def test_kv_dequant_vs_ref(n, width, group, bits, rng):
    rows, group = _group_rows(n, width, group)
    codes = rng.integers(0, 1 << bits, size=(rows, group)).astype(np.uint8)
    scales = rng.uniform(0.01, 0.2, (rows, 1)).astype(np.float32)
    zeros = rng.normal(size=(rows, 1)).astype(np.float32)
    out = kv_dequant(jnp.asarray(codes), jnp.asarray(scales),
                     jnp.asarray(zeros), interpret=True,
                     out_dtype=jnp.float32)
    ref = kv_dequant_ref(jnp.asarray(codes), jnp.asarray(scales),
                         jnp.asarray(zeros), out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    host = codes.astype(np.float32) * scales + zeros
    np.testing.assert_allclose(np.asarray(out), host, atol=1e-6)


@pytest.mark.parametrize("rows,group,rows_blk", [
    (74, 64, 16),      # 74 % 16 = 10: ragged final grid block
    (1020, 64, 64),    # 1020 % 64 = 60
    (513, 32, 128),    # four full blocks + a single ragged row
    (15, 64, 32),      # n < rows_blk entirely (rows_blk clamped)
])
def test_kv_dequant_ragged_grid(rows, group, rows_blk, rng):
    """n % rows_blk != 0: the final grid block is ragged; the kernel must
    still match the oracle exactly on every row."""
    bits = 5
    codes = rng.integers(0, 1 << bits, size=(rows, group)).astype(np.uint8)
    scales = rng.uniform(0.01, 0.2, (rows, 1)).astype(np.float32)
    zeros = rng.normal(size=(rows, 1)).astype(np.float32)
    out = kv_dequant(jnp.asarray(codes), jnp.asarray(scales),
                     jnp.asarray(zeros), rows_blk=rows_blk,
                     interpret=True, out_dtype=jnp.float32)
    ref = kv_dequant_ref(jnp.asarray(codes), jnp.asarray(scales),
                         jnp.asarray(zeros), out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    # ragged tiling must be exactly the single-block launch (same kernel,
    # no padding leakage into valid rows)
    whole = kv_dequant(jnp.asarray(codes), jnp.asarray(scales),
                       jnp.asarray(zeros), rows_blk=rows,
                       interpret=True, out_dtype=jnp.float32)
    assert np.array_equal(np.asarray(out), np.asarray(whole))


def _mixed_width_rows(rows, group, rng):
    """Rows of heterogeneous widths as quantize() would store them: each
    row's step is its span / (2^bits - 1), divided on the host."""
    bits = rng.choice([3, 4, 5, 6, 8], size=(rows, 1))
    codes = (rng.integers(0, 256, size=(rows, group)) %
             (1 << bits)).astype(np.uint8)
    spans = rng.uniform(0.1, 4.0, (rows, 1)).astype(np.float32)
    scales = (spans / ((1 << bits) - 1).astype(np.float32))
    zeros = rng.normal(size=(rows, 1)).astype(np.float32)
    return bits, codes, scales.astype(np.float32), zeros


@pytest.mark.parametrize("n,width,group,rows_blk", [
    (64, 128, 64, 32), (53, 256, 64, 16), (7, 128, 32, 256),
])
def test_kv_dequant_mixed_vs_ref(n, width, group, rows_blk, rng):
    """One launch over rows of heterogeneous bit widths vs the jnp and
    numpy oracles, exact in fp32 (ragged grids included)."""
    rows, group = _group_rows(n, width, group)
    _, codes, scales, zeros = _mixed_width_rows(rows, group, rng)
    out = kv_dequant(jnp.asarray(codes), jnp.asarray(scales),
                     jnp.asarray(zeros), rows_blk=rows_blk, interpret=True,
                     out_dtype=jnp.float32)
    ref = kv_dequant_ref(jnp.asarray(codes), jnp.asarray(scales),
                         jnp.asarray(zeros), out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    host = codes.astype(np.float32) * scales + zeros
    np.testing.assert_allclose(np.asarray(out), host, atol=1e-6)
    whole = kv_dequant(jnp.asarray(codes), jnp.asarray(scales),
                       jnp.asarray(zeros), rows_blk=rows, interpret=True,
                       out_dtype=jnp.float32)
    assert np.array_equal(np.asarray(out), np.asarray(whole))


def test_kv_dequant_mixed_uniform_bits_parity(rng):
    """A launch over rows of mixed widths is BIT-IDENTICAL, row for row,
    to one launch per width: rows never interact, so a chunk assembles
    the same whichever chunks share its launch."""
    rows, group = _group_rows(96, 256, 64)
    bits, codes, scales, zeros = _mixed_width_rows(rows, group, rng)
    mixed = np.asarray(kv_dequant(jnp.asarray(codes), jnp.asarray(scales),
                                  jnp.asarray(zeros), interpret=True,
                                  out_dtype=jnp.float32))
    for b in np.unique(bits):
        sel = bits[:, 0] == b
        single = kv_dequant(jnp.asarray(codes[sel]),
                            jnp.asarray(scales[sel]),
                            jnp.asarray(zeros[sel]), interpret=True,
                            out_dtype=jnp.float32)
        assert np.array_equal(mixed[sel], np.asarray(single))


def test_dequantize_chunks_mixed_parity(rng):
    """One launch over chunks of heterogeneous widths returns, per
    chunk, exactly the per-chunk launch (fp32) and stays within rtol
    1e-5 of the host dequantize at bf16."""
    shapes = [(64, 48), (7, 33), (128, 64), (19, 5)]
    widths = [8, 3, 5, 4]
    qts = [quantize(rng.normal(size=s).astype(np.float32), b, 64)
           for s, b in zip(shapes, widths)]
    mixed = dequantize_chunks(qts, interpret=True, out_dtype=jnp.float32)
    for qt, m in zip(qts, mixed):
        single = np.asarray(dequantize_chunk(qt, interpret=True,
                                             out_dtype=jnp.float32))
        assert np.array_equal(np.asarray(m), single)
        np.testing.assert_allclose(np.asarray(m), dequantize(qt),
                                   atol=1e-5)
    mixed_bf = dequantize_chunks(qts, interpret=True,
                                 out_dtype=jnp.bfloat16)
    for qt, m in zip(qts, mixed_bf):
        np.testing.assert_allclose(np.asarray(m, np.float32),
                                   dequantize(qt), rtol=1e-5,
                                   atol=qt.scales.max() * 0.02 + 1e-2)


def test_dequantize_chunks_ragged_tail_isolated(rng):
    """A chunk whose size is not a multiple of the group pads its tail
    group with zero codes; the padding never reaches the next chunk of
    the launch, and the outputs keep each chunk's own shape."""
    a = quantize(rng.normal(size=(5, 7)).astype(np.float32), 3, 64)
    b = quantize(rng.normal(size=(32, 32)).astype(np.float32), 8, 64)
    ma, mb = dequantize_chunks([a, b], interpret=True,
                               out_dtype=jnp.float32)
    (only_b,) = dequantize_chunks([b], interpret=True,
                                  out_dtype=jnp.float32)
    assert ma.shape == (5, 7) and mb.shape == (32, 32)
    assert np.array_equal(mb, only_b)
    np.testing.assert_allclose(ma, dequantize(a), atol=1e-5)


@settings(max_examples=15, deadline=None, derandomize=True)
@given(st.integers(50, 4000), st.integers(2, 8), st.sampled_from([32, 64]))
def test_dequant_roundtrip_hypothesis(n_vals, bits, group):
    rng = np.random.default_rng(n_vals * 31 + bits)
    x = rng.normal(size=n_vals).astype(np.float32)
    qt = quantize(x, bits, group)
    host = dequantize(qt)
    dev = np.asarray(dequantize_chunk(qt, interpret=True,
                                      out_dtype=jnp.float32))
    np.testing.assert_allclose(dev, host, atol=1e-5)
    # quantization error bounded by half a step per group
    assert np.abs(host - x).max() <= qt.scales.max() * 0.51 + 1e-6


@settings(max_examples=10, deadline=None, derandomize=True)
@given(st.integers(1, 3), st.integers(128, 512), st.booleans())
def test_block_sparse_hypothesis(bh, s, causal):
    s = (s // 128) * 128
    if s == 0:
        return
    kk = jax.random.split(jax.random.PRNGKey(s * bh), 3)
    q = jax.random.normal(kk[0], (bh, s, 64), jnp.float32)
    k = jax.random.normal(kk[1], (bh, s, 64), jnp.float32)
    v = jax.random.normal(kk[2], (bh, s, 64), jnp.float32)
    idx, cnt = _mask_for(q, k, 0.85, 128, 128, causal=causal)
    out = block_sparse_attention(q, k, v, idx, cnt, causal=causal,
                                 interpret=True)
    ref = block_sparse_attention_ref(q, k, v, idx, cnt, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
