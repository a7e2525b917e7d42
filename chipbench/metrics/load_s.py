"""Server layer: mean host seconds of a request's load phase
(``ServeResult.load_wall_s``: the planner, host entropy decode,
``kv_dequant`` and cache assembly, ending once the cache is on the
device)."""


def read(w):
    if not w.served:
        return None
    return sum(r.load_wall_s for r in w.served) / len(w.served)
