"""Operations and bytes the served path needs, from shapes alone.

A multiply-add counts as two operations. Only the matrix products are
counted (projections, attention scores and values, the output head):
norms, rotary embedding and softmax are a rounding error beside them.
What one token's forward and one streamed plane hold depends on the
architecture, and the configuration's model family counts it
(``spec.family``).
"""
from __future__ import annotations

from chipbench import spec


def token_forward_flops(conf: dict, keys: int) -> float:
    """One token's forward through every layer and the output head, its
    attention reading ``keys`` cached positions (itself included)."""
    return spec.family(conf).token_forward_flops(conf, keys)


def decode_flops(conf: dict, context: int, first: int, steps: int) -> float:
    """Forward operations of ``steps`` decode steps whose tokens sit at
    positions ``context + first`` onwards, after a cached context."""
    return sum(token_forward_flops(conf, context + first + i + 1)
               for i in range(steps))


def kv_dequant_bytes(rows: int, group: int, out_bytes: int) -> int:
    """Bytes one ``kv_dequant`` launch must move: ``rows`` groups of
    ``group`` uint8 codes in, a float32 scale and zero per group, and the
    dequantized values out."""
    return rows * group + rows * 2 * 4 + rows * group * out_bytes


def kv_dequant_rows(conf: dict, *, streamed: int, chunk_tokens: int,
                    group: int) -> int:
    """Rows of the one launch that dequantizes ``streamed`` chunks: a key
    and a value plane per chunk, one row per quantization group."""
    plane = spec.family(conf).kv_plane_values(conf, chunk_tokens)
    return streamed * 2 * (-(-plane // group))
