"""A CPU-sized cell: the served path's code at reduced widths."""
import json

from chipbench import spec
from chipbench.tests.conftest import ROOT

END_TO_END = [("ttft_p50_s", "s"), ("ttft_p95_s", "s"), ("tpot_ms", "ms"),
              ("setup_s", "s")]
PER_LAYER = ["load_s", "streamed_share", "kv_dequant_roofline", "ttft_mfu",
             "decode_mfu", "device_idle_share", "compiles_in_window"]
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def config(**sizes) -> dict:
    with open(ROOT / "chipbench" / "configs" / "qwen2.5-3b.json") as f:
        conf = json.load(f)
    conf.update(name="tiny", hidden_size=128, intermediate_size=256,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, vocab_size=2048)
    conf.update(sizes)
    conf["server"].update(chunk_tokens=64, q_block=32, kv_block=32,
                          quant_group=32)
    return conf


MIX = {"policy": "sparkv", "doc_tokens": 128, "question_tokens": [4, 8],
       "answer_tokens": [4, 8], "block": 2, "link_seed": 1}

# limits of the tiny cell, from CPU readings of one request at these
# sizes over eight seeds: sound runs read a widest logit error of at most
# 0.094 and a root mean square of at most 0.023, the float8 control at
# least 0.476 and 0.112. The token gaps do not separate here (sound runs
# up to 0.100 and 0.0151, the control as little as 0 on two seeds), so
# their limits only hold sound runs
LIMITS = {"max_logit_gap": 0.15, "mean_logit_gap": 0.02,
          "max_logit_err": 0.25, "rms_logit_err": 0.05}


def cell() -> spec.Cell:
    return spec.Cell(
        workload={"name": "tiny", "chips": 1}, config=config(), traffic=MIX,
        limits=dict(LIMITS),
        end_to_end=[{"name": n, "unit": u} for n, u in END_TO_END],
        per_layer=[{"name": n, "unit": "%"} for n in PER_LAYER])
