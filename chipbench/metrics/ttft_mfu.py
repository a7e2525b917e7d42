"""Model step up to the first token: forward operations of the question
tokens (the last of which yields the first answer token) over the cached
document, from shapes, over Σ TTFT at the chip's bf16 peak, in %."""
from chipbench import flops


def read(w):
    if not w.served:
        return None
    doc = w.traffic["doc_tokens"]
    need = sum(flops.decode_flops(w.config, doc, 0, len(r.question))
               for r in w.served)
    return 100.0 * need / (sum(r.ttft_s for r in w.served)
                           * w.peaks["bf16_flops"])
