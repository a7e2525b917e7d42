"""One cell's served path: set-up, warm-up, and the measured window.

The window drives ``SparKVServer.generate(cid, question, max_new,
policy=<mix's>, compare_exact=False)`` from a closed loop with one client.
``generate`` returns only phase totals, so the harness wraps the server
instance's decode-step callable: the call that feeds the first answer
token starts once that token is on the host, which dates the first token
and records every token fed. It keeps the logits the server decoded from:
the first answer token's as its decode step returned them, the others as
``_decode`` returned them, already on the host. It also wraps the
instance's load and decode phases in profiler spans, so a trace labels
the device's idle gaps.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from chipbench import generator


@dataclasses.dataclass(eq=False)
class Served:
    """One finished request of the window."""
    question: list
    max_new: int
    t_sent: float
    t_first: float             # first answer token on the host
    t_end: float               # last answer token on the host
    load_wall_s: float
    n_streamed: int
    n_computed: int
    fed: list                  # device arrays fed to the decode steps
    tokens: list               # the answer generate() returned
    logits: list               # one row per answer token, as decoded

    @property
    def ttft_s(self) -> float:
        return self.t_first - self.t_sent

    def answer(self) -> list:
        """Every answer token the server produced, the first included:
        the first is fed back to the step after the question and is not
        among the tokens ``generate`` returns."""
        return [int(t[0]) for t in self.fed[len(self.question):]] \
            + [int(self.tokens[-1])]

    def answer_logits(self, vocab: int):
        """The program's logits behind each token of ``answer()``:
        ``(answer tokens, vocab)`` float32."""
        return np.stack([np.asarray(x, np.float32).reshape(-1)[:vocab]
                         for x in self.logits])


@dataclasses.dataclass
class Window:
    """What the measured window produced, for the metric readers."""
    config: dict
    traffic: dict
    served: list               # Served, in order
    compiles: int              # compile events inside the window
    trace: Optional[object] = None   # trace.Summary with --trace 1
    peaks: Optional[dict] = None


class _StepClock:
    def __init__(self, step):
        self.step, self.calls = step, []
        self.keep, self.kept, self.later = -1, None, []

    def __call__(self, params, cache, token, pos):
        self.calls.append((time.perf_counter(), token))
        out = self.step(params, cache, token, pos)
        if len(self.calls) - 1 == self.keep:
            self.kept = out[0]          # the first answer token's logits
        return out


def _spanned(fn, name):
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def build_server(conf: dict, model, params, *, seed: int):
    from repro.configs import SparKVConfig
    from repro.serving.engine import SparKVServer

    sv = conf["server"]
    spcfg = SparKVConfig(
        chunk_tokens=sv["chunk_tokens"], q_block=sv["q_block"],
        kv_block=sv["kv_block"], quant_bits=sv["quant_bits"],
        quant_group=sv["quant_group"], alloc_schedule=sv["alloc_schedule"])
    srv = SparKVServer(model, params, spcfg, profile=sv["profile"],
                       network=sv["network"], seed=seed)
    clock = _StepClock(srv._decode_step)
    srv._decode_step = clock
    srv.load_context = _spanned(srv.load_context, "chipbench.load")
    decode = srv._decode

    def decode_kept(*a, **kw):
        toks, logits = decode(*a, **kw)
        clock.later = logits
        return toks, logits
    srv._decode = _spanned(decode_kept, "chipbench.decode")
    return srv, clock


def serve_one(srv, clock, cid, req: generator.Request, policy: str,
              seed: int) -> Served:
    import jax

    p = len(req.question)
    clock.calls, clock.keep, clock.kept, clock.later = [], p - 1, None, []
    t_sent = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.request"):
        res = srv.generate(cid, req.question, max_new=req.max_new,
                           policy=policy, compare_exact=False, seed=seed)
    t_end = time.perf_counter()
    return Served(
        question=[int(t) for t in req.question], max_new=req.max_new,
        t_sent=t_sent, t_first=clock.calls[p][0], t_end=t_end,
        load_wall_s=res.load_wall_s, n_streamed=res.n_streamed,
        n_computed=res.n_computed,
        fed=[t for _, t in clock.calls], tokens=list(res.tokens),
        logits=[clock.kept, *clock.later])


def window(srv, clock, cid, traffic: generator.Traffic, *, seconds: float,
           seed: int, compile_clock, log) -> tuple[list, float, int, int]:
    """Requests back to back until ``seconds`` have passed; a request
    sent inside the window completes and counts. Returns (served,
    seconds from the first send to the last answer, compiles inside,
    failed requests)."""
    import jax

    served, failed = [], 0
    c0 = compile_clock.count
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for req in traffic.requests:
            if time.perf_counter() - t0 >= seconds:
                break
            try:
                served.append(serve_one(srv, clock, cid, req,
                                        traffic.policy, seed))
            except Exception as e:          # a failed request ends the run
                failed += 1
                log(f"request {len(served)} failed: {e!r}")
                break
        else:
            log(f"the traffic ran out of requests before {seconds} s")
    return served, time.perf_counter() - t0, \
        compile_clock.count - c0, failed
