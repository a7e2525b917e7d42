"""Pallas TPU flash-decode (GQA, single token vs long KV cache).

Grid: (batch, kv_heads, n_kv_blocks); the q heads of one kv group
(g = hq/hkv rows) ride in one VMEM tile so the MXU does a (g, d) x
(d, bk) matmul per block — at g>=8 this keeps the MXU busy instead of
degrading to vector ops. Running softmax state lives in VMEM scratch
across the sequential innermost dimension; masked tail blocks are skipped
by comparing block start to kv_len (scalar prefetch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref,                               # scalar prefetch
            q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref,
            *, kv_block: int, scale: float, n_blocks: int):
    j = pl.program_id(2)
    kv_len = len_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * kv_block < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (g, d)
        k = k_ref[0, :, 0, :].astype(jnp.float32)      # (bk, d)
        v = v_ref[0, :, 0, :]                          # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = j * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(kpos < kv_len, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l_safe = jnp.where(l_ref[...] > 0, l_ref[...], 1.0)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("kv_block", "scale", "interpret"))
def decode_attention(q, k, v, kv_len, *, kv_block: int = 256,
                     scale: float | None = None, interpret: bool):
    """q: (b, hq, d); k/v: (b, skv, hkv, d); kv_len: int32 scalar.
    Returns (b, hq, d)."""
    b, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    n_blocks = -(-skv // kv_block)
    qg = q.reshape(b, hkv, g, d)
    kv_len_arr = jnp.asarray([kv_len], jnp.int32)

    kern = functools.partial(_kernel, kv_block=kv_block, scale=scale,
                             n_blocks=n_blocks)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, g, d),
                             lambda bi, hi, j, ln: (bi, hi, 0, 0)),
                pl.BlockSpec((1, kv_block, 1, d),
                             lambda bi, hi, j, ln: (bi, j, hi, 0)),
                pl.BlockSpec((1, kv_block, 1, d),
                             lambda bi, hi, j, ln: (bi, j, hi, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda bi, hi, j, ln: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(kv_len_arr, qg, k, v)
    return out.reshape(b, hq, d)
