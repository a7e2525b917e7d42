"""Wrapper: QuantizedTensor (wire format) -> KV values via the fused
Pallas dequant kernel."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.compression.quantize import QuantizedTensor
from repro.kernels import pallas_interpret
from repro.kernels.kv_dequant.kernel import kv_dequant


def group_rows(qt: QuantizedTensor) -> np.ndarray:
    """The kernel's row layout: (n_groups, group) uint8, one quantization
    group per row, the tail group zero-padded."""
    n_vals = int(np.prod(qt.shape))
    codes = np.zeros(qt.scales.shape[0] * qt.group, np.uint8)
    codes[:n_vals] = qt.codes
    return codes.reshape(-1, qt.group)


def dequantize_chunks(qts: list, *, interpret: bool | None = None,
                      out_dtype=jnp.bfloat16) -> list:
    """Dequantize streamed KV chunks, of any mix of bit widths, in ONE
    kernel launch: each chunk's groups become rows carrying their own
    scale and zero. All chunks must share the quantization group size.
    Returns one qt.shape numpy array per input; a chunk's values do not
    depend on which other chunks share the launch."""
    assert qts, "empty chunk list"
    group = qts[0].group
    assert all(q.group == group for q in qts), "heterogeneous group size"
    codes = np.concatenate([group_rows(qt) for qt in qts])
    scales = np.concatenate([qt.scales for qt in qts]).astype(np.float32)
    zeros = np.concatenate([qt.zeros for qt in qts]).astype(np.float32)
    out = np.asarray(kv_dequant(jnp.asarray(codes),
                                jnp.asarray(scales[:, None]),
                                jnp.asarray(zeros[:, None]),
                                interpret=pallas_interpret(interpret),
                                out_dtype=out_dtype))
    starts = np.cumsum([0] + [qt.scales.shape[0] for qt in qts])
    return [out[a:b].reshape(-1)[:int(np.prod(qt.shape))].reshape(qt.shape)
            for qt, a, b in zip(qts, starts, starts[1:])]


def dequantize_chunk(qt: QuantizedTensor, *, interpret: bool | None = None,
                     out_dtype=jnp.bfloat16) -> np.ndarray:
    """Dequantize one streamed KV chunk. Returns a qt.shape array."""
    return dequantize_chunks([qt], interpret=interpret,
                             out_dtype=out_dtype)[0]
