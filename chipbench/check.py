"""The comparison that decides ``correct``.

Once the window has closed, each sampled request is run through the
plain reference over document + question + the answer tokens the server
fed back, and compared at every answer position in two ways:

- the token gap: how far the reference's logit of the token the server
  produced lies below the reference's best logit there. Greedy decoding
  picks each token by the program's own logits, so a sound run reads a
  gap only where rounding and the 5-bit streamed KV reorder near-tied
  logits. Compared as the widest gap and the mean gap per answer token;
- the logit error: the program's own logits (those its decode steps
  returned) against the reference's, over the whole vocabulary. Compared
  as the widest absolute difference and the root mean square.

The control reads the same positions with the reference in float8
(``reference.make(fp8=True)``) in the program's place: the gap of the
token that the lower precision puts first, and its logits' error. Both
sides go through ``verdict`` against the same limits.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference

TAIL_ALIGN = 128
NUMBERS = ("max_logit_gap", "mean_logit_gap", "max_logit_err",
           "rms_logit_err")


def tail_tokens(mix: dict) -> int:
    """Room after the document for the longest question and answer."""
    need = mix["question_tokens"][1] + mix["answer_tokens"][1]
    return -(-need // TAIL_ALIGN) * TAIL_ALIGN


def sample(served: list, seed: int, k: int) -> list:
    """Indices of up to ``k`` of the finished requests, drawn from the
    seed, the one with the longest answer always among them."""
    n = len(served)
    if n <= k:
        return list(range(n))
    longest = max(range(n), key=lambda i: served[i].max_new)
    rest = [i for i in range(n) if i != longest]
    pick = np.random.default_rng(seed).choice(rest, k - 1, replace=False)
    return sorted([longest, *(int(i) for i in pick)])


def _compare(ref: np.ndarray, tokens, logits: np.ndarray) -> dict:
    """Per answer position: the token gap, the widest logit error and the
    mean squared logit error."""
    n = len(ref)
    d = logits - ref
    return {"gap": ref.max(-1) - ref[np.arange(n), tokens],
            "err": np.abs(d).max(-1), "sq": np.square(d).mean(-1)}


class Judge:
    """Reference (and, for the control, float8 reference) logits over one
    cell's padded sequence length, compiled once."""

    def __init__(self, conf: dict, mix: dict, *, control: bool = False):
        self.conf, self.doc = conf, mix["doc_tokens"]
        self.tail = tail_tokens(mix)
        kw = dict(lo=self.doc - 1, n=self.tail + 1)
        self.ref = reference.make(conf, **kw)
        self.ctl = reference.make(conf, fp8=True, **kw) if control else None

    def compare(self, params, document: np.ndarray, question: list,
                answer: list, logits: np.ndarray) -> dict:
        """The served answer and its ``logits`` (one row per answer token)
        against the reference at the answer's positions and, with the
        control, the control's own first choices and logits."""
        import jax.numpy as jnp

        vocab = self.conf["vocab_size"]
        if not all(0 <= t < vocab for t in answer) or \
                logits.shape != (len(answer), vocab):
            bad = np.full(len(answer), np.inf)
            return {"served": {"gap": bad, "err": bad, "sq": bad}}
        seq = np.zeros(self.doc + self.tail, np.int32)
        body = list(document.reshape(-1)) + list(question) + answer[:-1]
        seq[:len(body)] = body
        rows = len(question) + np.arange(len(answer))   # slice positions
        ref = np.asarray(self.ref(params, jnp.asarray(seq)))[rows]
        out = {"served": _compare(ref, answer, logits)}
        if self.ctl is not None:
            ctl = np.asarray(self.ctl(params, jnp.asarray(seq)))[rows]
            out["control"] = _compare(ref, ctl.argmax(-1), ctl)
        return out


def readings(compared: list, key: str) -> dict:
    """The numbers compared, over every judged answer token of a run."""
    if not compared:
        return dict.fromkeys(NUMBERS, float("inf"))
    g = {f: np.concatenate([x[key][f] for x in compared])
         for f in ("gap", "err", "sq")}
    return {"max_logit_gap": float(g["gap"].max()),
            "mean_logit_gap": float(g["gap"].mean()),
            "max_logit_err": float(g["err"].max()),
            "rms_logit_err": float(np.sqrt(g["sq"].mean()))}


def verdict(read: dict, limits: dict, **exact) -> tuple[dict, bool]:
    """Each number that ``limits`` names beside its limit, then the exact
    counts in ``exact`` (limit 0); correct when none is over its limit."""
    checks = {name: {"value": read[name], "limit": limit}
              for name, limit in limits.items()}
    checks.update({name: {"value": v, "limit": 0}
                   for name, v in exact.items()})
    return checks, all(c["value"] <= c["limit"] for c in checks.values())
