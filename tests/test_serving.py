"""End-to-end serving: concrete KV assembly through the real
quantize->Huffman->dequant path; response fidelity vs the exact cache."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SparKVConfig, get_smoke
from repro.kernels.kv_dequant.ops import dequantize_chunks
from repro.models import build_model
from repro.serving.engine import SparKVServer


@pytest.fixture(scope="module")
def server():
    cfg = get_smoke("sparkv-qwen3-4b", layers=3, d_model=64, heads=4,
                    d_ff=128, vocab=256)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    spcfg = SparKVConfig(chunk_tokens=32, q_block=16, kv_block=16,
                         quant_group=32)
    srv = SparKVServer(model, params, spcfg, chunk_tokens=32)
    rng = np.random.default_rng(0)
    ctx = rng.integers(0, cfg.vocab_size, size=(1, 96))
    cid = srv.register_context(ctx)
    return srv, cid, rng


def test_register_context_compresses(server):
    srv, cid, _ = server
    st = srv.contexts[cid]
    raw = st.exact_k.nbytes + st.exact_v.nbytes
    assert st.wl.total_bytes() < raw / 3       # 5-bit + entropy < fp32/3


@pytest.mark.parametrize("policy", ["sparkv", "cachegen", "local_prefill",
                                    "strong_hybrid"])
def test_serve_fidelity(server, policy):
    srv, cid, rng = server
    prompt = rng.integers(0, 256, size=3)
    res = srv.generate(cid, prompt, max_new=5, policy=policy, seed=1)
    assert res.top1_agreement >= 0.8
    assert res.mean_kl < 0.5
    n = srv.contexts[cid].n_chunks
    assert res.n_streamed + res.n_computed == n
    if policy == "local_prefill":
        assert res.n_streamed == 0 and res.top1_agreement == 1.0


def test_streamed_bitstreams_roundtrip_exactly(server):
    """Every streamed chunk decodes to exactly the quantized codes."""
    srv, cid, _ = server
    # load_context asserts bitstream equality internally
    cache, res = srv.load_context(cid, policy="cachegen")
    assert res.engine.n_streamed == srv.contexts[cid].n_chunks
    # quantization error bound: cache vs exact within 5-bit step
    st = srv.contexts[cid]
    err = np.abs(np.asarray(cache["k"], np.float32) - st.exact_k).max()
    scale_bound = max(np.abs(st.exact_k).max(),
                      np.abs(st.exact_v).max()) / 31
    assert err <= scale_bound * 2 + 1e-4


def test_loaded_cache_is_the_stored_codes_assembled(server):
    """The cache ``load_context`` builds from the decoded bitstreams is, bit
    for bit, the one the stored codes give through the same dequant
    launch, scatter into the exact cache and bfloat16 cast."""
    srv, cid, _ = server
    st = srv.contexts[cid]
    cache, res = srv.load_context(cid, policy="cachegen")
    streamed = sorted(res.engine.streamed_set)
    assert len(streamed) == st.n_chunks
    qts = [qt for c in streamed for qt in st.encoded[c][2:]]
    outs = dequantize_chunks(qts, interpret=srv.interpret,
                             out_dtype=jnp.float32)
    k, v = st.exact_k.copy(), st.exact_v.copy()
    ct = srv.chunk_tokens
    for c, kd, vd in zip(streamed, outs[0::2], outs[1::2]):
        k[c.l, 0, c.t * ct:(c.t + 1) * ct] = kd
        v[c.l, 0, c.t * ct:(c.t + 1) * ct] = vd
    for name, want in (("k", k), ("v", v)):
        want = np.asarray(jnp.asarray(want, jnp.bfloat16))
        got = np.asarray(cache[name])
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got.view(np.uint16), want.view(np.uint16))


def test_utilization_tracking(server):
    srv, _, _ = server
    assert srv.utilization() == 0.0


def test_serve_fleet_concurrent_contexts(server):
    """Registered contexts submitted into the multi-request cluster."""
    srv, cid, _ = server
    jobs = [(cid, 0.0, "sparkv"), (cid, 0.0, "cachegen"),
            (cid, 0.05, "local_prefill")]
    rep = srv.serve_fleet(jobs, closed_loop=True)
    assert len(rep.records) == 3
    n = srv.contexts[cid].n_chunks
    for r in rep.records:
        assert r.n_streamed + r.n_computed == n
        assert r.ttft_s > 0 and r.energy_j > 0
    s = rep.summary()
    assert s["goodput_rps"] > 0 and s["ttft_p50_s"] <= s["ttft_p99_s"]
