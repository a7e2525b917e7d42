"""trace.py on a small recorded trace: busy time as the union of device
operations, the idle share, operations found by name, idle gaps labelled
by the innermost host span."""
import pytest
from jax.profiler import ProfileData

from chipbench import trace

# one TPU plane (a while loop at 1-4 us runs fusion.1 at 1-2 and
# 2.5-4 us; kv_dequant runs 6-7 us) and the harness's host spans; window
# 0.5-10.5 us
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 1500000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] ()" } }
  event_metadata { key: 2 value { id: 2 name: "kv_dequant_kernel" } }
  event_metadata { key: 5 value { id: 5 name: "%while.3 = (s32[]) while()" } }
  event_metadata { key: 3 value { id: 3 name: "jit_decode_step(7)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_kv_dequant(9)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 7
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 3500000 }
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.load" } }
  event_metadata { key: 3 value { id: 3 name: "chipbench.decode" } }
  event_metadata { key: 4 value { id: 4 name: "unrelated" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    devices, spans = trace.read_profile(pd)
    assert set(devices) == {"/device:TPU:0"}
    assert [s[0] for s in spans] == ["chipbench.window", "chipbench.load",
                                     "chipbench.decode"]
    return trace.reduce(devices, spans)


def test_busy_is_the_union_of_device_ops(summary):
    assert summary.window_s == pytest.approx(10e-6)
    # ops cover 1-4 us and 6-7 us: 4 us, the loop and its body once
    assert summary.busy_s == pytest.approx(4e-6)
    assert summary.idle_share == pytest.approx(0.6)


def test_ops_and_modules_found_by_name(summary):
    # the loop encloses its body and is not counted; each op is named
    # after the module it ran in
    assert summary.op_s == pytest.approx(
        {"jit_decode_step/fusion.1": 2.5e-6,
         "jit_kv_dequant/kv_dequant_kernel": 1e-6})
    assert summary.module_s == pytest.approx(
        {"jit_decode_step": 3e-6, "jit_kv_dequant": 1e-6})
    assert summary.device_seconds("kv_dequant") == pytest.approx(1e-6)
    assert summary.device_seconds("absent") == 0


def test_idle_gaps_labelled_by_innermost_span(summary):
    # gaps 0.5-1, 4-6 (mid 5: load) and 7-10.5 (mid 8.75: decode)
    assert [g[0] for g in summary.gaps] == ["chipbench.decode",
                                            "chipbench.load", "none"]
    assert [g[1] for g in summary.gaps] == pytest.approx([3.5e-6, 2e-6,
                                                          0.5e-6])
    bd = summary.breakdown()
    assert bd["device_ops"][0] == ["jit_decode_step/fusion.1",
                                   pytest.approx(2.5e-6)]
    assert [n for n, _ in bd["idle_gaps"]] == ["chipbench.decode",
                                               "chipbench.load", "none"]


def test_union_merges_touching_and_nested():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4), (5.5, 5.7)]) == \
        [(0, 4), (5, 6)]


def test_an_op_enclosing_others_is_busy_but_not_a_leaf():
    ops = trace._named_ops(
        [("%while.1 = (s32[]) while(..)", 0, 10), ("%fusion.2 = f32[]", 1, 3),
         ("%fusion.3 = f32[]", 4, 6)], [("jit_step", 0, 10)])
    assert [(n, leaf) for n, _, _, leaf in ops] == [
        ("jit_step/while.1", False), ("jit_step/fusion.2", True),
        ("jit_step/fusion.3", True)]
    s = trace.reduce({"d": {"ops": ops}}, [], window=(0, 20))
    assert s.busy_s == pytest.approx(10e-9)
    assert s.op_s == pytest.approx({"jit_step/fusion.2": 2e-9,
                                    "jit_step/fusion.3": 2e-9})


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.reduce({"d": {"ops": [("x", 0, 1, True)]}}, [])
