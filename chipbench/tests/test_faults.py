"""A whole run at a reduced size, the chip check skipped: a sound run is
correct, each fault planted in the timed path under the harness makes
``correct`` come out false, and so does the float8 control put in the
program's place, judged by the same limits. A window of 1 ms serves
exactly one request; on seed 5 the planner streams chunks, so every fault
bites."""
import numpy as np
import pytest

from chipbench import run, serve
from chipbench.tests import tiny


def _run(monkeypatch, fault=None, seed=5, control=False):
    build = serve.build_server

    def faulty_build(*a, **kw):
        srv, steps = build(*a, **kw)
        if fault:
            fault(srv, steps)
        return srv, steps

    monkeypatch.setattr(serve, "build_server", faulty_build)
    return run.run_cell(tiny.cell(), seed=seed, seconds=0.001, trace=False,
                        dev=tiny.CPU, log=lambda m: None, control=control)


def _token_altered(srv, steps):
    """Each produced token replaced by the least likely one."""
    step = steps.step

    def altered(*a):
        logits, cache = step(*a)
        return logits.at[0, logits[0].argmin()].set(1e4), cache
    steps.step = altered


def _state_unchanged(srv, steps):
    """The decode step hands back the cache it was given: no token's
    key and value reach the tail."""
    step = steps.step

    def stale(params, cache, token, pos):
        kept = {k: v.copy() for k, v in cache.items()}
        logits, _ = step(params, cache, token, pos)
        return logits, kept
    steps.step = stale


def _streamed_kv_lost(srv, steps):
    """The load leaves streamed chunks' keys at zero."""
    load = srv.load_context

    def lossy(cid, **kw):
        cache, res = load(cid, **kw)
        if res.engine.n_streamed:
            cache = dict(cache, k=cache["k"] * 0)
        return cache, res
    srv.load_context = lossy


def _answer_rewritten(srv, steps):
    """generate() returns an answer other than the one it decoded."""
    gen = srv.generate

    def rewritten(*a, **kw):
        res = gen(*a, **kw)
        res.tokens = (np.asarray(res.tokens) + 1) % 2048
        return res
    srv.generate = rewritten


def test_sound_run_is_correct(monkeypatch):
    res = _run(monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p50_s", "ttft_p95_s", "tpot_ms",
                                   "setup_s"}
    assert list(res)[-1] == "checks"
    for name, limit in tiny.LIMITS.items():
        assert res["checks"][name]["limit"] == limit
        assert res["checks"][name]["value"] <= limit


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _streamed_kv_lost, _answer_rewritten])
def test_fault_makes_the_run_incorrect(monkeypatch, fault):
    res = _run(monkeypatch, fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
def test_control_is_incorrect(monkeypatch, seed):
    res = _run(monkeypatch, seed=seed, control=True)
    assert res["correct"], res["checks"]
    ctl = res["control"]
    assert not ctl["correct"], ctl["checks"]
    assert set(ctl["checks"]) == set(tiny.LIMITS)
    for name, c in ctl["checks"].items():
        assert c["limit"] == tiny.LIMITS[name]
        assert c["value"] == ctl["readings"][name]
