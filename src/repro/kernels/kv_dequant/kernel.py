"""Pallas TPU fused KV-chunk dequantization.

Streamed chunks arrive as uint8 symbol planes (post entropy decode) plus
per-group fp32 scales/zeros; this kernel fuses dequantize + cast to bf16
on-chip so the host never materializes an fp32 copy (on the paper's edge
path this was the PCIe-attached "device transfer" slice of Fig. 16 — on
TPU the dequant runs where the cache lives).

Layout: one quantization group per row. codes are (n_groups, group)
uint8 and the affine parameters are (n_groups, 1) fp32 columns, so the
per-group broadcast is a lane broadcast and the kernel needs no
in-register reshape (Mosaic refuses a (rows, g*group) -> (rows, g, group)
shape cast). The codes go uint8 -> int32 -> fp32: Mosaic has no direct
uint8 -> fp32 cast. Rows are tiled in VMEM-sized blocks.

Rows are independent, so one launch dequantizes chunks of different bit
widths: each row's step is the host-computed scale quantize() stored.
The step is never derived on the chip (span / (2^bits - 1)): TPU fp32
division is not correctly rounded, and such a row would differ by an
ulp from the same chunk dequantized alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(c_ref, s_ref, z_ref, o_ref):
    c = c_ref[...].astype(jnp.int32).astype(jnp.float32)
    o_ref[...] = (c * s_ref[...] + z_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("rows_blk", "interpret", "out_dtype"))
def kv_dequant(codes, scales, zeros, *, interpret: bool,
               rows_blk: int = 512, out_dtype=jnp.bfloat16):
    """codes: (n, group) uint8, one group per row; scales/zeros: (n, 1)
    float32 -> (n, group) out_dtype."""
    n, group = codes.shape
    rows_blk = min(rows_blk, n)
    row = lambda i: (i, 0)
    return pl.pallas_call(
        _kernel,
        grid=(-(-n // rows_blk),),
        in_specs=[pl.BlockSpec((rows_blk, group), row),
                  pl.BlockSpec((rows_blk, 1), row),
                  pl.BlockSpec((rows_blk, 1), row)],
        out_specs=pl.BlockSpec((rows_blk, group), row),
        out_shape=jax.ShapeDtypeStruct((n, group), out_dtype),
        interpret=interpret,
    )(codes, scales, zeros)
