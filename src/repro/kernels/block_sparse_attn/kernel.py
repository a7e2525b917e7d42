"""Pallas TPU block-sparse flash attention (SpargeAttention, TPU-adapted).

GPU original skips work at warp granularity; the TPU adaptation tiles
q x kv in 128x128 MXU-aligned blocks, walks a per-(head, q-block) list of
active kv-block indices delivered via scalar prefetch (so the DMA pipeline
can fetch the right K/V tiles ahead of compute), and keeps the flash
running-softmax state (m, l, acc) in VMEM scratch across the innermost
grid dimension.

Grid: (batch*q_heads, n_q_blocks, max_active_blocks). TPU grid iteration
is sequential over the last dimension, which makes the scratch-carried
softmax recurrence legal; `interpret=True` preserves those semantics on
CPU for validation. `interpret` is required: the caller chooses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(idx_ref, cnt_ref,                      # scalar prefetch
            q_ref, k_ref, v_ref,                   # VMEM blocks
            o_ref,                                 # output block
            m_ref, l_ref, acc_ref,                 # VMEM scratch
            *, causal: bool, q_block: int, kv_block: int, scale: float,
            max_nnz: int):
    bh = pl.program_id(0)
    qb = pl.program_id(1)
    j = pl.program_id(2)
    cnt = cnt_ref[bh, qb]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < cnt)
    def _compute():
        kb = idx_ref[bh, qb, j]
        q = q_ref[0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0].astype(jnp.float32)           # (bk, d)
        v = v_ref[0]                                # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qb * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (q_block, kv_block), 0)
            kpos = kb * kv_block + jax.lax.broadcasted_iota(
                jnp.int32, (q_block, kv_block), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = m_new

    @pl.when(j == max_nnz - 1)
    def _finalize():
        any_row = m_ref[...] > NEG_INF / 2
        l_safe = jnp.where(l_ref[...] > 0, l_ref[...], 1.0)
        out = acc_ref[...] / l_safe[:, None]
        out = jnp.where(any_row[:, None], out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "q_block", "kv_block", "scale",
                              "kv_group", "interpret"))
def block_sparse_attention(q, k, v, block_idx, block_cnt, *,
                           causal: bool = True, q_block: int = 128,
                           kv_block: int = 128, scale: float | None = None,
                           kv_group: int = 1, interpret: bool):
    """q: (bh, sq, d); k/v: (bh_kv, skv, d) with bh == bh_kv * kv_group
    (GQA: q row bh reads kv row bh // kv_group).
    block_idx: (bh, n_qb, max_nnz) int32; block_cnt: (bh, n_qb) int32.
    """
    bh, sq, d = q.shape
    skv = k.shape[1]
    n_qb = sq // q_block
    max_nnz = block_idx.shape[-1]
    scale = scale if scale is not None else d ** -0.5

    grid = (bh, n_qb, max_nnz)
    kern = functools.partial(_kernel, causal=causal, q_block=q_block,
                             kv_block=kv_block, scale=scale,
                             max_nnz=max_nnz)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, q_block, d),
                             lambda bh, qb, j, idx, cnt: (bh, qb, 0)),
                pl.BlockSpec((1, kv_block, d),
                             lambda bh, qb, j, idx, cnt:
                             (bh // kv_group, idx[bh, qb, j], 0)),
                pl.BlockSpec((1, kv_block, d),
                             lambda bh, qb, j, idx, cnt:
                             (bh // kv_group, idx[bh, qb, j], 0)),
            ],
            out_specs=pl.BlockSpec((1, q_block, d),
                                   lambda bh, qb, j, idx, cnt: (bh, qb, 0)),
            scratch_shapes=[
                pltpu.VMEM((q_block,), jnp.float32),       # running max
                pltpu.VMEM((q_block,), jnp.float32),       # running sum
                pltpu.VMEM((q_block, d), jnp.float32),     # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
    )(block_idx, block_cnt, q, k, v)
