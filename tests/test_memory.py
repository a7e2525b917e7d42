"""KV memory server: residency accounting, eviction policy ordering,
reload-planner cost parity with the core cost models, evict-to-lower-bits
round-trips through the quantizer and the fused dequant kernel, and
end-to-end cluster behaviour — unbounded tracking is bit-identical to a
memory-less fleet, finite budgets evict and reload without losing
requests, and admission gating queues rather than deadlocks."""
import numpy as np
import pytest

from repro.compression.quantize import (BITRATE_LEVELS, dequantize,
                                        quant_error, quantize)
from repro.configs import SparKVConfig, get_config
from repro.core.costs import (DISK_TIERS, MemoryModel, PROFILES,
                              RunQueueModel, t_disk_read)
from repro.core.engine import context_kv_bytes, token_kv_bytes
from repro.serving.cluster import (RequestSpec, ServingCluster,
                                   telemetry_policy)
from repro.serving.decode import DecodeConfig
from repro.serving.memory import KVMemoryServer, plan_reload
from repro.serving.resources import DiskServer

CFG = get_config("sparkv-qwen3-4b")
SP = SparKVConfig(scheduler_mode="engine")
PROF = PROFILES["jetson-orin"]
GB = 1e9


def make_cluster(**kw):
    kw.setdefault("max_concurrency", 8)
    return ServingCluster(CFG, SP, "jetson-orin", "campus-wifi", **kw)


def _specs(n, out=24, ctx=4096):
    return [RequestSpec(context_len=ctx, arrival_s=0.1 * i, device=0,
                        max_new_tokens=out) for i in range(n)]


# ---------------------------------------------------------------------------
# residency accounting
# ---------------------------------------------------------------------------

def test_charge_release_accounting():
    m = KVMemoryServer(MemoryModel(capacity_bytes=None))
    m.admit(0, 0.0)
    m.admit(1, 0.0)
    m.charge(0, 1.0 * GB, 1.0)
    m.charge(1, 0.5 * GB, 2.0)
    m.charge(0, 0.25 * GB, 3.0)
    assert np.isclose(m.resident_bytes(), 1.75 * GB)
    assert np.isclose(m.peak_resident, 1.75 * GB)
    assert m.pressure() == 0.0                    # unbounded
    m.release(0, 4.0)
    assert np.isclose(m.resident_bytes(), 0.5 * GB)
    m.release(1, 5.0)
    assert m.resident_bytes() == 0.0
    assert np.isclose(m.freed_total, m.charged_total)
    assert abs(m.ledger_balance()) < 1.0


def test_kv_byte_model_matches_config():
    """context/token KV byte helpers: per-token bytes times context
    equals the context total, and SSM-style configs pin decode growth
    to zero."""
    ctx = 8192
    total = context_kv_bytes(CFG, ctx)
    per_tok = token_kv_bytes(CFG)
    assert total > 0 and per_tok > 0
    assert np.isclose(total, per_tok * ctx, rtol=1e-9)


def test_time_weighted_percentile():
    m = KVMemoryServer(MemoryModel(capacity_bytes=None))
    m.admit(0, 0.0)
    m.charge(0, 1.0 * GB, 0.0)      # 1 GB held for 99 s
    m.charge(0, 9.0 * GB, 99.0)     # 10 GB held for 1 s
    m.release(0, 100.0)
    assert np.isclose(m.resident_percentile(50), 1.0 * GB)
    assert m.resident_percentile(99.9) >= 9.0 * GB


# ---------------------------------------------------------------------------
# eviction policies
# ---------------------------------------------------------------------------

def _loaded_server(model, n=3, each=1.0 * GB):
    m = KVMemoryServer(model)
    for r in range(n):
        m.admit(r, float(r))
        m.charge(r, each, float(r))
        m.mark_ready(r, float(r))    # t_last_use = r: rid 0 is LRU
    return m


def test_lru_evicts_least_recently_used():
    m = _loaded_server(MemoryModel(capacity_bytes=3.0 * GB, policy="lru",
                                   disk=None))
    m.touch(0, 10.0)                 # rid 1 becomes the LRU victim
    m.admit(3, 11.0)
    evs = m.charge(3, 1.0 * GB, 11.0)
    assert [e.rid for e in evs] == [1]
    assert evs[0].action == "drop"   # no disk tier configured
    assert m.needs_reload(1)
    assert m.resident_total <= 3.0 * GB + 1.0
    assert abs(m.ledger_balance()) < 1.0


def test_idle_policy_prefers_parked_sequences():
    """With an idle set, the most-recently-used parked sequence still
    loses to any member of the active batch."""
    m = _loaded_server(MemoryModel(capacity_bytes=3.0 * GB, policy="idle",
                                   disk=None))
    m.touch(0, 10.0)                 # rid 0 is the *most* recent
    m.admit(3, 11.0)
    evs = m.charge(3, 1.0 * GB, 11.0, idle=frozenset({0}))
    assert [e.rid for e in evs] == [0]


def test_pinned_rids_are_never_victims():
    m = _loaded_server(MemoryModel(capacity_bytes=3.0 * GB, policy="lru",
                                   disk=None))
    m.admit(3, 11.0)
    evs = m.charge(3, 1.0 * GB, 11.0, pinned=frozenset({0, 1}))
    assert [e.rid for e in evs] == [2]
    # pin everyone: the server over-commits rather than deadlocking
    m2 = _loaded_server(MemoryModel(capacity_bytes=3.0 * GB, policy="lru",
                                    disk=None))
    m2.admit(3, 11.0)
    evs2 = m2.charge(3, 1.0 * GB, 11.0, pinned=frozenset({0, 1, 2}))
    assert evs2 == [] and m2.resident_total > 3.0 * GB


def test_bits_policy_downgrades_in_place_then_demotes():
    """Evict-to-lower-bits walks the victim down the quantization ladder
    without suspending it; only at the ladder floor does it demote."""
    m = _loaded_server(MemoryModel(capacity_bytes=3.0 * GB, policy="bits"),
                       n=3)
    m.admit(3, 11.0)
    evs = m.charge(3, 1.0 * GB, 11.0)
    assert evs and all(e.action == "downgrade" for e in evs)
    assert not any(m.needs_reload(r) for r in range(3))   # nobody parked
    first = evs[0]
    assert first.bits == BITRATE_LEVELS[0]                # 16 -> 8
    assert np.isclose(m.bits_of(first.rid) / 16.0,
                      (1.0 * GB - first.freed_bytes) / (1.0 * GB))
    # crush the budget: ladders bottom out at 3 bits, then demote/drop
    evs = m.charge(3, 3.0 * GB, 12.0)
    assert any(e.action in ("demote", "drop") for e in evs) \
        or m.resident_total > m.capacity   # or everyone is at the floor
    assert abs(m.ledger_balance()) < 1.0


def test_bits_cold_pool_downgrades_cold_share_first():
    """cold_frac < 1: pressure requantizes only the victim's cold pool
    until it floors; the hot remainder keeps its width (and the record's
    bits_of stays the hot width)."""
    m = _loaded_server(MemoryModel(capacity_bytes=3.0 * GB, policy="bits",
                                   cold_frac=0.5, disk=None), n=3)
    m.admit(3, 11.0)
    evs = m.charge(3, 0.2 * GB, 11.0)
    assert evs and evs[0].action == "downgrade"
    # cold pool = 0.5 GB at 16 bits -> 8 bits frees exactly 0.25 GB
    assert np.isclose(evs[0].freed_bytes, 0.25 * GB)
    assert evs[0].bits == BITRATE_LEVELS[0]
    assert m.bits_of(evs[0].rid) == 16     # hot pool untouched
    assert abs(m.ledger_balance()) < 1.0
    # keep crushing: the cold pool floors at 3 bits before any hot-pool
    # downgrade, then the hot pool walks, then demote/drop
    evs = m.charge(3, 3.5 * GB, 12.0)
    seen_hot = [e for e in evs
                if e.action == "downgrade" and m.bits_of(e.rid) < 16]
    floored = [e for e in evs if e.action in ("demote", "drop")]
    assert seen_hot or floored or m.resident_total > m.capacity
    assert abs(m.ledger_balance()) < 1.0


def test_bits_cold_frac_default_is_whole_resident():
    """cold_frac defaults to 1.0 = the legacy whole-resident downgrade:
    first eviction frees bytes * (1 - 8/16) in one step."""
    assert MemoryModel().cold_frac == 1.0
    m = _loaded_server(MemoryModel(capacity_bytes=3.0 * GB, policy="bits",
                                   disk=None), n=3)
    m.admit(3, 11.0)
    evs = m.charge(3, 0.2 * GB, 11.0)
    assert np.isclose(evs[0].freed_bytes, 0.5 * GB)
    assert m.bits_of(evs[0].rid) == 8


def test_bits_cold_pool_conservation_under_pressure_storm():
    """The charged == resident + disk + dropped + freed ledger holds
    through interleaved cold-pool downgrades, demotions and reloads."""
    m = _loaded_server(MemoryModel(capacity_bytes=2.0 * GB, policy="bits",
                                   cold_frac=0.3, disk="ufs-3.1"), n=2)
    for i, extra in enumerate([0.5, 1.0, 2.0, 4.0]):
        rid = 10 + i
        m.admit(rid, 20.0 + i)
        m.charge(rid, extra * GB, 20.0 + i)
        m.mark_ready(rid, 20.0 + i)
        assert abs(m.ledger_balance()) < 1.0
    for rid in list(m._res):
        if m.needs_reload(rid):
            m.begin_reload(rid, 30.0)
            m.finish_reload(rid, 31.0)
            assert abs(m.ledger_balance()) < 1.0


def test_bits_growth_lands_at_downgraded_width():
    m = _loaded_server(MemoryModel(capacity_bytes=3.0 * GB, policy="bits",
                                   disk=None), n=3)
    m.admit(3, 11.0)
    m.charge(3, 1.0 * GB, 11.0)      # downgrades rid 0 to 8 bits
    assert m.bits_of(0) == 8
    before = m.resident_total
    m.charge(0, 1.0 * GB, 12.0)      # decode growth: charged at 8/16
    assert np.isclose(m.resident_total - before, 0.5 * GB, rtol=1e-6) \
        or m.resident_total <= m.capacity + 1.0   # unless it re-evicted


# ---------------------------------------------------------------------------
# demote / reload through the disk tier
# ---------------------------------------------------------------------------

def test_demote_reload_roundtrip_through_disk():
    m = _loaded_server(MemoryModel(capacity_bytes=3.0 * GB, policy="lru",
                                   disk="ufs-3.1"))
    m.admit(3, 11.0)
    evs = m.charge(3, 1.0 * GB, 11.0)
    assert evs[0].action == "demote"
    rid = evs[0].rid
    assert np.isclose(m.disk_total, 1.0 * GB)
    assert m.disk.bytes_written == pytest.approx(1.0 * GB)
    ev = m.begin_reload(rid, 12.0)
    assert ev.from_disk and np.isclose(ev.nbytes, 1.0 * GB)
    m.release(3, 13.0)               # make room for the restore
    m.finish_reload(rid, 14.0)
    assert m.disk_total == 0.0 and not m.needs_reload(rid)
    assert np.isclose(m._res[rid].bytes, 1.0 * GB)
    assert abs(m.ledger_balance()) < 1.0


def test_disk_server_serializes():
    prof = MemoryModel(disk="ufs-3.1").disk_profile
    d = DiskServer(prof)
    t1 = d.submit(1.0 * GB, 0.0, op="write")
    t2 = d.submit(1.0 * GB, 0.0, op="read")
    assert t1 == pytest.approx(prof.latency_s + 1.0 * GB / prof.write_bw)
    assert t2 == pytest.approx(t1 + prof.latency_s + 1.0 * GB / prof.read_bw)
    assert d.backlog_s(0.0) == pytest.approx(t2)
    assert d.backlog_s(t2 + 1.0) == 0.0


# ---------------------------------------------------------------------------
# reload planner: cost parity with the core models
# ---------------------------------------------------------------------------

# sized so the three paths cost the same order of magnitude (disk read
# bandwidth is ~100x the radio link, so the resident bytes dominate)
WIRE, RES, COMP = 8e6, 0.8e9, 0.7
DISK_PROF = MemoryModel(disk="ufs-3.1").disk_profile


def test_plan_reload_pure_mode_cost_parity():
    bw = 20e6
    chunk = (WIRE, RES, COMP)
    p = plan_reload([chunk], mode="restream", profile=PROF, stream_bw=bw)
    assert p.makespan_s == pytest.approx(WIRE / bw + PROF.t_proc(WIRE))
    assert p.n_stream == 1 and p.stream_bytes == WIRE
    p = plan_reload([chunk], mode="recompute", profile=PROF, stream_bw=bw,
                    comp_wait_s=0.3)
    assert p.makespan_s == pytest.approx(0.3 + COMP)
    assert p.n_comp == 1 and p.comp_s == COMP
    p = plan_reload([chunk], mode="disk", profile=PROF, stream_bw=bw,
                    disk=DISK_PROF, has_disk_copy=True)
    assert p.makespan_s == pytest.approx(t_disk_read(RES, DISK_PROF))
    assert p.n_disk == 1 and p.disk_bytes == RES
    # disk mode without a demoted copy falls back to restream
    p = plan_reload([chunk], mode="disk", profile=PROF, stream_bw=bw,
                    disk=None, has_disk_copy=False)
    assert p.n_stream == 1 and p.n_disk == 0


def test_planner_beats_single_paths_on_balanced_chunks():
    """With several identical chunks, spreading across the overlapping
    paths always projects a shorter makespan than any single path."""
    bw = 20e6
    chunks = [(WIRE, RES, COMP)] * 8
    kw = dict(profile=PROF, stream_bw=bw, disk=DISK_PROF,
              has_disk_copy=True)
    full = plan_reload(chunks, mode="planner", **kw)
    for mode in ("restream", "recompute", "disk"):
        pure = plan_reload(chunks, mode=mode, **kw)
        assert full.makespan_s <= pure.makespan_s + 1e-9
    assert full.n_stream + full.n_comp + full.n_disk == 8
    # at least two paths genuinely used
    assert sum(1 for n in (full.n_stream, full.n_comp, full.n_disk)
               if n > 0) >= 2


def test_planner_respects_backlog_seeds():
    """A path's live backlog steers chunks away from it: seed the comp
    path heavily and the planner must stop assigning to it."""
    bw = 20e6
    chunks = [(WIRE, RES, 0.01)] * 4          # compute looks very cheap
    free = plan_reload(chunks, mode="planner", profile=PROF, stream_bw=bw)
    assert free.n_comp == 4
    busy = plan_reload(chunks, mode="planner", profile=PROF, stream_bw=bw,
                       comp_wait_s=100.0)
    assert busy.n_comp == 0


# ---------------------------------------------------------------------------
# evict-to-lower-bits fidelity: ladder round-trip + fused dequant kernel
# ---------------------------------------------------------------------------

def test_bits_ladder_roundtrip_and_kernel():
    """Requantizing down the ladder degrades monotonically, and the
    fused kv_dequant kernel reproduces the quantizer's reconstruction
    for the resident codes at every ladder level."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.kv_dequant.kernel import kv_dequant
    from repro.kernels.kv_dequant.ref import kv_dequant_ref
    rng = np.random.default_rng(7)
    group, rows, width = 64, 8, 256
    kv = rng.standard_normal((rows, width)).astype(np.float32)
    errs = []
    for bits in BITRATE_LEVELS:
        qt = quantize(kv, bits, group)
        errs.append(np.sqrt(np.mean((dequantize(qt) - kv) ** 2)))
        # the kernel's layout: one quantization group per row
        codes = qt.codes.reshape(-1, group)
        scales = qt.scales[:, None]
        zeros = qt.zeros[:, None]
        out = np.asarray(kv_dequant(codes, scales, zeros, interpret=True),
                         np.float32).reshape(rows, width)
        ref = np.asarray(kv_dequant_ref(jnp.asarray(codes),
                                        jnp.asarray(scales),
                                        jnp.asarray(zeros)),
                         np.float32).reshape(rows, width)
        # one bf16 ulp: interpret-mode rounding at the cast boundary
        np.testing.assert_allclose(out, ref, rtol=2 ** -7, atol=1e-6)
        np.testing.assert_allclose(out, dequantize(qt).reshape(rows, width),
                                   atol=0.05)    # bf16 rounding only
    # coarser resident bits -> strictly worse reconstruction
    assert all(a <= b + 1e-9 for a, b in zip(errs, errs[1:]))
    assert quant_error(kv, 3, group) > quant_error(kv, 8, group)


# ---------------------------------------------------------------------------
# end-to-end cluster behaviour
# ---------------------------------------------------------------------------

def test_unbounded_tracking_is_bit_identical():
    """A passive (capacity=None) memory server must not perturb the
    fleet: every record field identical, only the telemetry block is
    added to the summary."""
    specs = _specs(4)
    r0 = make_cluster().run(specs)
    r1 = make_cluster(memory=MemoryModel(capacity_bytes=None)).run(specs)
    for a, b in zip(r0.records, r1.records):
        assert a.ttft_s == b.ttft_s
        assert a.ttlt_s == b.ttlt_s
        assert a.energy_j == b.energy_j
        assert a.n_streamed == b.n_streamed
        assert b.n_evictions == 0 and b.reload_s == 0.0
        assert b.kv_bits == 16
    s0, s1 = r0.summary(), r1.summary()
    assert "peak_resident_bytes" not in s0
    assert s1["peak_resident_bytes"] > 0
    assert s1["goodput_tok_s"] == s0["goodput_tok_s"]
    assert r0.memory is None and r1.memory is not None


def test_finite_budget_evicts_reloads_and_completes():
    specs = _specs(5, out=32)
    peak = make_cluster(memory=MemoryModel(capacity_bytes=None)) \
        .run(specs).summary()["peak_resident_bytes"]
    rep = make_cluster(
        memory=MemoryModel(capacity_bytes=0.5 * peak)).run(specs)
    s = rep.summary()
    assert len(rep.records) == len(specs)
    assert s["n_evictions"] > 0 and s["n_reloads"] > 0
    assert s["reload_s_total"] > 0
    assert any(r.reload_s > 0 for r in rep.records)
    assert any(r.n_evictions > 0 for r in rep.records)
    assert rep.memory["peak_resident_bytes"] <= peak + 1.0
    # eviction stalls show up where they belong: the tail got slower
    assert s["ttlt_p99_s"] >= make_cluster().run(specs) \
        .summary()["ttlt_p99_s"] - 1e-9


def test_finite_budget_with_run_queue_and_bits():
    specs = _specs(5, out=32)
    peak = make_cluster(memory=MemoryModel(capacity_bytes=None)) \
        .run(specs).summary()["peak_resident_bytes"]
    rep = make_cluster(
        run_queue=RunQueueModel(1, "fifo"),
        decode=DecodeConfig(max_batch=4),
        memory=MemoryModel(capacity_bytes=0.5 * peak,
                           policy="bits")).run(specs)
    assert len(rep.records) == len(specs)
    assert rep.memory["n_downgrades"] > 0
    assert any(r.kv_bits < 16 for r in rep.records)


def test_admission_gate_queues_then_drains():
    """A tight gate_frac holds arrivals while residency is projected
    over budget but never deadlocks: the fleet still finishes every
    request, and the gate never holds an empty fleet."""
    specs = _specs(5)
    peak = make_cluster(memory=MemoryModel(capacity_bytes=None)) \
        .run(specs).summary()["peak_resident_bytes"]
    gated = make_cluster(
        memory=MemoryModel(capacity_bytes=0.6 * peak,
                           gate_frac=0.8)).run(specs)
    free = make_cluster(
        memory=MemoryModel(capacity_bytes=0.6 * peak)).run(specs)
    assert len(gated.records) == len(specs)
    # gating trades queue wait for eviction churn
    assert gated.summary()["n_evictions"] \
        <= free.summary()["n_evictions"]
    assert gated.summary()["queue_wait_p99_s"] \
        >= free.summary()["queue_wait_p99_s"] - 1e-9


def test_memory_budget_sugar():
    specs = _specs(3)
    r = make_cluster(memory_budget=2.0 * GB).run(specs)
    assert r.memory is not None
    assert r.memory["peak_resident_bytes"] > 0


# ---------------------------------------------------------------------------
# decode-aware telemetry policy
# ---------------------------------------------------------------------------

class _StubCluster:
    """Duck-typed stand-in exposing exactly the live signals
    telemetry_policy reads."""
    capacity = 2
    decode_cfg = DecodeConfig(max_batch=8)

    def __init__(self, frac=0.1, load=0, occ=0, pressure=0.0):
        self._frac, self._load = frac, load
        self._occ, self._pressure = occ, pressure

    def projected_flow_frac(self, device):
        return self._frac

    def device_load(self, device):
        return self._load

    def decode_occupancy(self, device):
        return self._occ

    def memory_pressure(self, device):
        return self._pressure


def test_telemetry_policy_memory_and_decode_vetoes():
    spec = RequestSpec(context_len=4096, arrival_s=0.0)
    # starved link + idle device: local prefill
    assert telemetry_policy(spec, _StubCluster()) == "local_prefill"
    # memory pressure above the ceiling vetoes the switch
    assert telemetry_policy(
        spec, _StubCluster(pressure=0.95)) == "sparkv"
    # a full decode batch vetoes it too
    assert telemetry_policy(spec, _StubCluster(occ=8)) == "sparkv"
    # both signals below their ceilings: the veto lifts
    assert telemetry_policy(
        spec, _StubCluster(pressure=0.5, occ=3)) == "local_prefill"


def test_disk_tier_catalog():
    for name, _ in DISK_TIERS.items():
        prof = MemoryModel(disk=name).disk_profile
        assert prof.read_bw > 0 and prof.write_bw > 0
        assert t_disk_read(1.0 * GB, prof) > 1.0 * GB / prof.read_bw
