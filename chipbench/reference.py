"""The plain reference: the configuration's forward pass in float32
``jax.numpy`` at the highest matmul precision, with no kernels, no cache
and no batching, over document + question + answer at once. Each model
family writes its own (``families/<family>.py`` ``make_reference``); it
imports nothing of the program and reads only the weights the benchmark
drew.

``fp8=True`` is the control: the same forward with float8 e4m3 inputs to
every weight matmul, the step below the configuration's bfloat16 that
would tempt a later change.
"""
from __future__ import annotations

from chipbench import spec


def make(conf: dict, *, lo: int, n: int, fp8: bool = False):
    """A jitted ``fn(params, tokens) -> (n, vocab) float32 logits`` of the
    positions ``lo .. lo + n - 1``; ``tokens`` is ``(s,)`` int32."""
    return spec.family(conf).make_reference(conf, lo=lo, n=n, fp8=fp8)
