"""Planner layer: the share of (chunk, layer) units the planner streamed,
Σ streamed / Σ (streamed + computed), in %. Under ``sparkv`` the compute
leg copies the exact cache and costs nothing, so a shift of this share
towards compute shortens TTFT without a real speed-up."""


def read(w):
    total = sum(r.n_streamed + r.n_computed for r in w.served)
    if not total:
        return None
    return 100.0 * sum(r.n_streamed for r in w.served) / total
