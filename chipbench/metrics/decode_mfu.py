"""Model step, decode: forward operations of the answer tokens fed back
after the first, from shapes, over Σ (last token - first token) host
seconds at the chip's bf16 peak, in %."""
from chipbench import flops


def read(w):
    busy = sum(r.t_end - r.t_first for r in w.served)
    if not busy:
        return None
    doc = w.traffic["doc_tokens"]
    need = sum(flops.decode_flops(w.config, doc, len(r.question), r.max_new)
               for r in w.served)
    return 100.0 * need / (busy * w.peaks["bf16_flops"])
