"""The plain reference against the served path at a reduced size: the
server's prefill (registration), cache assembly (streamed chunks through
Huffman and kv_dequant under sparkv; the exact cache under local_prefill)
and decode steps give logits within the tolerance of the float32
reference, and the float8 control does not."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import generator, reference, serve, weights
from chipbench.tests import tiny

# bf16 weights and activations move a logit (std about 1 here) by up to
# 0.075 and the 5-bit streamed KV by 0.02 more (CPU, seeds 3-5); float8
# inputs to every weight matmul move it by 0.5 and more
TOLERANCE = 0.2
SEEDS = [3, 4]


def _logits(seed, policy):
    conf = tiny.config()
    model = weights.program_model(conf)
    params = weights.draw(conf, seed)
    srv, steps = serve.build_server(conf, model, params, seed=1)
    outs = []
    step = steps.step

    def recording(*a):
        logits, cache = step(*a)
        outs.append(np.asarray(logits[0], np.float32)[:conf["vocab_size"]])
        return logits, cache

    steps.step = recording
    traffic = generator.make(tiny.MIX, vocab=conf["vocab_size"], seed=seed)
    cid = srv.register_context(traffic.document)
    r = serve.serve_one(srv, steps, cid, traffic.requests[0], policy, 1)
    doc = traffic.document.shape[1]
    fed = [int(t[0]) for t in r.fed]
    seq = np.zeros(doc + 128, np.int32)
    seq[:doc + len(fed)] = list(traffic.document[0]) + fed
    rows = 1 + np.arange(len(fed))          # positions doc .. in the slice
    ref, f8 = (np.asarray(reference.make(conf, lo=doc - 1, n=129, fp8=c)(
        params, jnp.asarray(seq)))[rows] for c in (False, True))
    return r, np.stack(outs), ref, f8


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("policy", ["sparkv", "local_prefill"])
def test_served_logits_match_the_reference(seed, policy):
    r, prog, ref, f8 = _logits(seed, policy)
    assert (r.n_streamed > 0) == (policy == "sparkv")
    assert np.abs(prog - ref).max() < TOLERANCE
    assert np.abs(f8 - ref).max() > TOLERANCE


def test_reference_is_causal_and_position_aware():
    conf = tiny.config()
    params = weights.draw(conf, 0)
    fn = reference.make(conf, lo=0, n=8)
    a = np.arange(16, dtype=np.int32)
    b = a.copy()
    b[10:] = 7                              # change only the future
    la, lb = (np.asarray(fn(params, jnp.asarray(x))) for x in (a, b))
    np.testing.assert_array_equal(la, lb)
    c = np.roll(a[:8], 1)
    lc = np.asarray(fn(params, jnp.asarray(np.concatenate([c, a[8:]]))))
    assert np.abs(lc - la).max() > 0.1
