"""The one traffic generator: turns a mix's parameters and a seed into
the cell's documents and its stream of requests.

A mix file ``traffic/<mix>.json`` holds:

- ``policy``: the loading policy every request asks for;
- ``doc_tokens``: the length of the one registered document (a multiple
  of the configuration's chunk);
- ``question_tokens``, ``answer_tokens``: ``[lo, hi]`` ranges;
- ``block``: how many requests make one block. Every block holds the same
  ``block`` question lengths and the same ``block`` answer lengths, evenly
  spaced over their ranges; the seed only orders them within each block
  and draws the token ids. So every seed sends the same work, in another
  order, and a window of a few requests sees the whole range;
- ``max_prompt_plus_answer``: a cap the generator checks, where the mix
  has one (the program's decode tail holds question and answer together);
- ``link_seed``: the seed of the bandwidth trace the planner replays,
  the same in every run, since the link belongs to the deployment and
  not to the request.

``about`` says in words what the mix stands for.

Requests come one after another from a closed loop with one client and no
think time: an on-device user waits for each answer.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    question: np.ndarray       # token ids
    max_new: int               # answer tokens the server decodes


@dataclasses.dataclass
class Traffic:
    policy: str
    document: np.ndarray       # (1, doc_tokens)
    warmup: Request
    requests: list             # the window's requests, in order


def _levels(lo: int, hi: int, n: int) -> np.ndarray:
    return np.rint(np.linspace(lo, hi, n)).astype(int)


N_REQUESTS = 512        # more than any window of 51 s can serve


def make(mix: dict, *, vocab: int, seed: int) -> Traffic:
    rng = np.random.default_rng(seed)
    block = int(mix["block"])
    q_lv = _levels(*mix["question_tokens"], block)
    a_lv = _levels(*mix["answer_tokens"], block)
    cap = mix.get("max_prompt_plus_answer")
    if cap is not None and q_lv.max() + a_lv.max() > cap:
        raise ValueError(f"question + answer can reach "
                         f"{q_lv.max() + a_lv.max()} > {cap} tokens")
    document = rng.integers(0, vocab, size=(1, int(mix["doc_tokens"])))

    def request(q, a):
        return Request(question=rng.integers(0, vocab, size=int(q)),
                       max_new=int(a))

    warmup = request(q_lv[0], a_lv[0])
    reqs = []
    while len(reqs) < N_REQUESTS:
        for q, a in zip(rng.permutation(q_lv), rng.permutation(a_lv)):
            reqs.append(request(q, a))
    return Traffic(policy=mix["policy"], document=document, warmup=warmup,
                   requests=reqs[:N_REQUESTS])
