"""jit'd public wrapper around the block-sparse attention kernel.

`sparse_prefill_attention` is the full pipeline the serving engine uses
for locally-computed chunks: estimate block importance -> select blocks at
98% mass -> run the Pallas kernel (interpret mode as
`repro.kernels.pallas_interpret` resolves it). The pure-jnp oracle lives
in ref.py.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import pallas_interpret
from repro.kernels.block_sparse_attn.kernel import block_sparse_attention
from repro.kernels.block_sparse_attn.ref import block_sparse_attention_ref
from repro.sparse.mask import block_scores, select_blocks


def sparse_prefill_attention(q, k, v, *, mass: float = 0.98,
                             q_block: int = 128, kv_block: int = 128,
                             causal: bool = True,
                             use_ref: bool = False,
                             interpret: bool | None = None):
    """q: (b, s, hq, d); k/v: (b, s, hkv, d). Returns ((b, s, hq, d),
    block_cnt) — the per-row active-block counts feed the latency
    predictor's `s` feature."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)

    # scores need q rows matched to their kv head
    kf_rep = jnp.repeat(kf, g, axis=0) if g > 1 else kf
    scores = block_scores(qf, kf_rep, q_block=q_block, kv_block=kv_block,
                          causal=causal)
    idx, cnt = select_blocks(scores, mass=mass, q_block=q_block,
                             kv_block=kv_block)
    if use_ref:
        vf_rep = jnp.repeat(vf, g, axis=0) if g > 1 else vf
        o = block_sparse_attention_ref(qf, kf_rep, vf_rep, idx, cnt,
                                       causal=causal, q_block=q_block,
                                       kv_block=kv_block)
    else:
        o = block_sparse_attention(qf, kf, vf, idx, cnt, causal=causal,
                                   q_block=q_block, kv_block=kv_block,
                                   kv_group=g,
                                   interpret=pallas_interpret(interpret))
    o = o.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
    return o, cnt.reshape(b, hq, s // q_block)
