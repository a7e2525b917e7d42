"""Operations and bytes the served path needs, from shapes alone.

A multiply-add counts as two operations. Only the matrix products are
counted (projections, attention scores and values, the output head):
norms, rotary embedding and softmax are a rounding error beside them.
"""
from __future__ import annotations


def token_forward_flops(conf: dict, keys: int) -> float:
    """One token's forward through every layer and the output head, its
    attention reading ``keys`` cached positions (itself included)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    proj = 2 * d * (hq + 2 * hkv) * hd + 2 * hq * hd * d
    attn = 2 * 2 * hq * hd * keys
    mlp = 2 * 3 * d * f
    return conf["num_hidden_layers"] * (proj + attn + mlp) \
        + 2 * d * conf["vocab_size"]


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
    plane = chunk_tokens * conf["num_key_value_heads"] * conf["head_dim"]
    return streamed * 2 * (-(-plane // group))
