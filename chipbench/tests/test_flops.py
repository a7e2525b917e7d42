"""flops.py against counts made by hand."""
import json

import pytest

from chipbench import flops, spec
from chipbench.tests.conftest import ROOT

CONF = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 2, "num_hidden_layers": 3,
        "vocab_size": 10}


def test_token_forward_flops_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8 -> 2*(64+32+32+64) = 384;
    # scores and values over 5 keys: 2 * 2 * (4*2) * 5 = 160;
    # gate, up, down: 2 * 3 * 8 * 16 = 768; head: 2 * 8 * 10 = 160
    assert flops.token_forward_flops(CONF, 5) == 3 * (384 + 160 + 768) + 160


def test_decode_flops_counts_growing_keys():
    # tokens at positions 100+2, 100+3: they read 103 and 104 keys
    want = flops.token_forward_flops(CONF, 103) + \
        flops.token_forward_flops(CONF, 104)
    assert flops.decode_flops(CONF, 100, 2, 2) == want
    assert flops.decode_flops(CONF, 100, 0, 0) == 0


def test_kv_dequant_rows_and_bytes():
    # 3 chunks of 4 tokens x 2 kv heads x 2 dims = 16 values per plane,
    # group 8: 2 rows per plane, a key and a value plane per chunk
    assert flops.kv_dequant_rows(CONF, streamed=3, chunk_tokens=4,
                                 group=8) == 12
    # per row: 8 code bytes, 8 bytes of scale and zero, 8 x 4 out
    assert flops.kv_dequant_bytes(12, 8, 4) == 12 * (8 + 8 + 32)


def test_a_partial_group_is_one_row():
    assert flops.kv_dequant_rows(CONF, streamed=1, chunk_tokens=3,
                                 group=8) == 2 * 2


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (ROOT / "chipbench" / "configs").glob("*.json")))
def test_published_token_flops_near_twice_the_parameters(name):
    with open(ROOT / "chipbench" / "configs" / f"{name}.json") as f:
        conf = json.load(f)
    n = spec.family(conf).token_params(conf)
    # every matrix a token reads is read once; norms and biases are not
    # matrix products, attention over one key is a rounding error
    assert flops.token_forward_flops(conf, 1) == pytest.approx(2 * n,
                                                               rel=1e-3)
