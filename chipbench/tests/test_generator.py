"""The traffic generator: a seed repeats its traffic, another seed
changes the token ids and the order but never the work."""
import json

import numpy as np
import pytest

from chipbench import generator
from chipbench.tests.conftest import ROOT

MIXES = ["docqa-1k", "docqa-2k", "chat-1k"]


def _mix(name):
    with open(ROOT / "chipbench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _sizes(t):
    return [(len(r.question), r.max_new) for r in t.requests]


@pytest.mark.parametrize("name", MIXES)
def test_a_seed_repeats_its_traffic(name):
    a = generator.make(_mix(name), vocab=151936, seed=2**31 + 9)
    b = generator.make(_mix(name), vocab=151936, seed=2**31 + 9)
    assert np.array_equal(a.document, b.document)
    assert _sizes(a) == _sizes(b)
    assert all(np.array_equal(x.question, y.question)
               for x, y in zip(a.requests, b.requests))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_change_tokens_and_order_not_work(name):
    mix = _mix(name)
    a = generator.make(mix, vocab=151936, seed=1)
    b = generator.make(mix, vocab=151936, seed=2)
    assert not np.array_equal(a.document, b.document)
    assert _sizes(a) != _sizes(b)
    k = mix["block"]
    for i in range(0, len(a.requests), k):
        qa = sorted(s[0] for s in _sizes(a)[i:i + k])
        qb = sorted(s[0] for s in _sizes(b)[i:i + k])
        assert qa == qb
        assert sorted(s[1] for s in _sizes(a)[i:i + k]) == \
            sorted(s[1] for s in _sizes(b)[i:i + k])
    lo, hi = mix["question_tokens"]
    assert min(s[0] for s in _sizes(a)) == lo
    assert max(s[0] for s in _sizes(a)) == hi
    assert a.document.shape == (1, mix["doc_tokens"])
    assert a.policy == mix["policy"]


def test_a_cap_on_question_plus_answer_is_checked():
    mix = dict(_mix("chat-1k"), max_prompt_plus_answer=100)
    with pytest.raises(ValueError, match="> 100"):
        generator.make(mix, vocab=100, seed=0)
