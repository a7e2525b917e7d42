"""Kernel layer: ``kv_dequant``'s share of its roofline, in %: the bytes
its launches must move (from shapes) at the chip's peak HBM bandwidth,
over the device seconds of its events in the trace. Memory bound: two
operations per value against at least 5 bytes per value."""
from chipbench import flops

OUT_BYTES = 4          # the server dequantizes into float32


def read(w):
    if w.trace is None:
        return None
    seconds = w.trace.device_seconds("kv_dequant")
    sv = w.config["server"]
    need = sum(flops.kv_dequant_bytes(
        flops.kv_dequant_rows(w.config, streamed=r.n_streamed,
                              chunk_tokens=sv["chunk_tokens"],
                              group=sv["quant_group"]),
        sv["quant_group"], OUT_BYTES) for r in w.served if r.n_streamed)
    if not seconds or not need:
        return None
    return 100.0 * need / w.peaks["hbm_bytes_s"] / seconds
