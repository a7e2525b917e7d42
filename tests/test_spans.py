"""The served path's profiler spans: one request served under
``jax.profiler`` leaves the ``sparkv.*`` span tree and its counters in the
``.xplane.pb``, and the first-token timestamp falls between the load's end
and the decode's."""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import SparKVConfig, get_smoke
from repro.models import build_model
from repro.serving.engine import SparKVServer

CHUNK = 32


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = get_smoke("sparkv-qwen3-4b", layers=3, d_model=64, heads=4,
                    d_ff=128, vocab=256)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    spcfg = SparKVConfig(chunk_tokens=CHUNK, q_block=16, kv_block=16,
                         quant_group=32)
    srv = SparKVServer(model, params, spcfg, chunk_tokens=CHUNK)
    rng = np.random.default_rng(0)
    cid = srv.register_context(rng.integers(0, cfg.vocab_size, (1, 96)))
    prompt = rng.integers(0, cfg.vocab_size, size=3)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        res = srv.generate(cid, prompt, max_new=4, policy="cachegen",
                           compare_exact=False, seed=1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    spans = sorted(
        (ev.start_ns, -ev.end_ns, ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("sparkv."))
    spans = [(name, s, -neg_e, stats) for s, neg_e, name, stats in spans]
    return srv, cfg, cid, prompt, res, spans


def _named(spans, name):
    return [sp for sp in spans if sp[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_one_request_holds_the_load_and_the_decode(traced):
    _, _, _, _, _, spans = traced
    (req,) = _named(spans, "sparkv.request")
    assert req[3] == {"request": 1, "policy": "cachegen"}
    (load,) = _named(spans, "sparkv.load")
    (decode,) = _named(spans, "sparkv.decode")
    assert _inside(load, req) and _inside(decode, req)
    assert load[2] <= decode[1]
    for name in ("plan", "copy_exact", "entropy_decode", "dequant",
                 "scatter", "upload"):
        found = _named(spans, f"sparkv.load.{name}")
        assert found and all(_inside(sp, load) for sp in found), name
    (init,) = _named(spans, "sparkv.decode.init_cache")
    assert _inside(init, decode)
    assert not _named(spans, "sparkv.compare_exact")


def test_counters_come_from_the_planner_and_shapes(traced):
    srv, cfg, cid, _, res, spans = traced
    st = srv.contexts[cid]
    assert res.n_streamed == st.n_chunks    # cachegen streams every chunk
    (plan,) = _named(spans, "sparkv.load.plan")
    assert plan[3] == {"streamed": res.n_streamed,
                       "computed": res.n_computed, "migrations": 0}
    plane = CHUNK * cfg.num_kv_heads * cfg.head_dim
    # one lockstep decode of every streamed K and V plane
    (codec,) = _named(spans, "sparkv.load.entropy_decode")
    planes = 2 * res.n_streamed
    encs = [e for ek, ev, _, _ in st.encoded.values() for e in (ek, ev)]
    assert codec[3] == {
        "values": planes * plane,
        "nbytes": sum(e.payload_bytes() for e in encs),
        "planes": planes, "lanes": 64 * planes, "steps": -(-plane // 64),
        "table_bits": max(int(e.code.lengths.max()) for e in encs)}
    (copy,) = _named(spans, "sparkv.load.copy_exact")
    assert copy[3] == {"nbytes": st.exact_k.nbytes + st.exact_v.nbytes}
    (dequant,) = _named(spans, "sparkv.load.dequant")
    values = 2 * res.n_streamed * plane
    assert dequant[3]["d2h_bytes"] == 4 * values
    assert dequant[3]["h2d_bytes"] == values + 8 * values // 32
    (upload,) = _named(spans, "sparkv.load.upload")
    assert upload[3] == {"h2d_bytes": st.exact_k.size * 2 * 2}


def test_a_step_span_per_fed_token(traced):
    srv, cfg, cid, prompt, _, spans = traced
    s = srv.contexts[cid].tokens.shape[1]
    (decode,) = _named(spans, "sparkv.decode")
    question = _named(spans, "sparkv.step.question")
    answer = _named(spans, "sparkv.step.answer")
    assert [sp[3]["pos"] for sp in question] == \
        list(range(s, s + len(prompt)))
    assert [sp[3]["pos"] for sp in answer] == \
        list(range(s + len(prompt), s + len(prompt) + 4))
    assert all(_inside(sp, decode) for sp in question + answer)
    assert question[-1][2] <= answer[0][1]
    row = cfg.padded_vocab * np.dtype(srv.params["emb"].dtype).itemsize
    assert {sp[3]["d2h_bytes"] for sp in question + answer} == {row}


def test_first_token_lies_between_load_and_decode_end(traced):
    *_, res, _ = traced
    assert res.load_wall_s <= res.first_token_s <= \
        res.load_wall_s + res.decode_wall_s
    assert len(res.tokens) == 4
