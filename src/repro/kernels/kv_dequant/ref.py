"""Oracle for fused KV dequantization: uint8 codes + per-group scale/zero
-> bf16, matching repro.compression.quantize semantics."""
from __future__ import annotations

import jax.numpy as jnp


def kv_dequant_ref(codes, scales, zeros, *, out_dtype=jnp.bfloat16):
    """codes: (n, group) uint8, one group per row; scales/zeros: (n, 1)
    float32. Returns (n, group) out_dtype."""
    x = codes.astype(jnp.float32) * scales + zeros
    return x.astype(out_dtype)
