"""SparKV serving engine — the end-to-end inference driver.

Context-reuse serving: a reusable context is registered once ("cloud"
side: exact KV + per-chunk quantized+Huffman bitstreams + chunk stats);
each request then *loads* that context through a policy pipeline
(sparkv / strong_hybrid / cachegen / local_prefill):

  - timing & energy come from the discrete-event engine (virtual clock,
    real compressed bytes, ground-truth compute latencies);
  - the KV cache content is assembled *concretely*: streamed chunks are
    entropy-decoded + dequantized (Pallas kv_dequant kernel), computed
    chunks take the exact local values — so response-quality numbers are
    real logit comparisons, not a proxy table.

The device-utilization signal the paper reads from nvidia-smi is exposed
here as `utilization()` (active requests / capacity) and feeds the
latency predictor's U feature. For *timing under concurrency* that static
signal is superseded by `serve_fleet()`, which submits registered
contexts into `repro.serving.cluster.ServingCluster`: N loads share the
link through the bandwidth arbiter and couple compute latencies through
closed-loop utilization.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.compression import huffman
from repro.compression.quantize import QuantizedTensor, quantize
from repro.configs.base import SparKVConfig
from repro.core import baselines as B
from repro.core.chunks import Chunk
from repro.core.costs import NETWORKS
from repro.data.workloads import WorkloadChunks
from repro.kernels.kv_dequant.ops import dequantize_chunks
from repro.models.api import Model


@dataclasses.dataclass
class StoredContext:
    tokens: np.ndarray                 # (1, S)
    exact_k: np.ndarray                # (L, 1, S, hkv, hd)
    exact_v: np.ndarray
    encoded: dict                      # Chunk(t,l,0) -> (enc_k, enc_v, qt_k, qt_v)
    wl: WorkloadChunks
    n_chunks: int


@dataclasses.dataclass
class ServeResult:
    ttft_s: float
    energy_j: float
    tokens: np.ndarray
    top1_agreement: float
    mean_kl: float
    n_streamed: int
    n_computed: int
    wall_s: float
    # host-clock phase times of this request, each ending once its device
    # work is done (ttft_s above is the planner's virtual-clock estimate)
    load_wall_s: float
    decode_wall_s: float
    # generate() start -> the first answer token's argmax on the host
    first_token_s: float


class SparKVServer:
    def __init__(self, model: Model, params, spcfg: SparKVConfig,
                 *, profile: str = "jetson-orin",
                 network: str = "campus-wifi", capacity: int = 8,
                 chunk_tokens: Optional[int] = None, seed: int = 0,
                 interpret: Optional[bool] = None):
        self.model = model
        self.params = params
        self.spcfg = spcfg
        self.profile = profile
        self.network = network
        self.capacity = capacity
        self.chunk_tokens = chunk_tokens or spcfg.chunk_tokens
        self.seed = seed
        # Pallas interpret mode of the dequant kernels; None lets
        # repro.kernels.pallas_interpret decide by backend
        self.interpret = interpret
        self.contexts: dict[int, StoredContext] = {}
        self.active_requests = 0
        self._next_id = 0
        self._n_requests = 0          # serial of generate() calls
        self._first_token_at = None   # host clock, set by _decode
        self._prefill = jax.jit(self.model.prefill)
        self._decode_step = jax.jit(self.model.decode_step,
                                    donate_argnums=(1,))

    def utilization(self) -> float:
        return min(self.active_requests / self.capacity, 1.0)

    # ---------------- cloud side ----------------
    def register_context(self, tokens: np.ndarray) -> int:
        """Precompute exact KV + compressed chunk artifacts (cloud).

        With ``spcfg.alloc_schedule`` armed, the artifacts are encoded
        at per-chunk widths: a first base-width quantization pass
        measures the entropy signal (``huffman.entropy_bits`` of the
        real code planes — this also populates the workload's
        ``entropy_bits``, which was a zero placeholder), the allocator
        turns (attention mass x entropy) saliency into per-chunk bits,
        and any chunk allocated off the base width is re-quantized at
        its own width before entropy coding. The "uniform" sentinel
        takes the single-pass path unchanged."""
        cfg = self.model.cfg
        assert tokens.shape[0] == 1, "one context per registration"
        s = tokens.shape[1]
        ct = self.chunk_tokens
        assert s % ct == 0, f"context length must be a multiple of {ct}"
        _, cache = self._prefill(self.params,
                                 {"tokens": jnp.asarray(tokens)})
        k = np.asarray(cache["k"], np.float32)      # (L, 1, S, hkv, hd)
        v = np.asarray(cache["v"], np.float32)
        n_t, n_l = s // ct, cfg.num_layers

        # pass 1: base-width quantization + the measured entropy signal
        quant = {}
        ent = np.zeros((n_l, 1))
        for t in range(n_t):
            for l in range(n_l):
                kc = k[l, 0, t * ct:(t + 1) * ct]
                vc = v[l, 0, t * ct:(t + 1) * ct]
                qk = quantize(kc, self.spcfg.quant_bits, self.spcfg.quant_group)
                qv = quantize(vc, self.spcfg.quant_bits, self.spcfg.quant_group)
                quant[Chunk(t, l, 0)] = (qk, qv)
                ent[l, 0] += (huffman.entropy_bits(qk.codes, 1 << qk.bits)
                              + huffman.entropy_bits(qv.codes, 1 << qv.bits)
                              ) / (2 * n_t)

        # per-chunk allocation: re-quantize off-base chunks at their own
        # width (the "flat" schedule allocates base everywhere, so the
        # artifacts stay byte-identical to an unarmed registration)
        active = self._measure_active_blocks(tokens, n_t, n_l)
        if getattr(self.spcfg, "alloc_schedule", "uniform") != "uniform":
            from repro.compression.allocate import (allocate_bits,
                                                    schedule_of)
            bits_arr = allocate_bits(
                active, ent, self.spcfg.quant_bits,
                schedule_of(self.spcfg.alloc_schedule))
            for c, (qk, qv) in list(quant.items()):
                b = int(bits_arr[c.t, c.l, 0])
                if b != self.spcfg.quant_bits:
                    kc = k[c.l, 0, c.t * ct:(c.t + 1) * ct]
                    vc = v[c.l, 0, c.t * ct:(c.t + 1) * ct]
                    quant[c] = (quantize(kc, b, self.spcfg.quant_group),
                                quantize(vc, b, self.spcfg.quant_group))

        encoded = {}
        chunk_bytes = np.zeros((n_t, n_l, 1))
        for c, (qk, qv) in quant.items():
            ek = huffman.encode(qk.codes, 1 << qk.bits, n_streams=64)
            ev = huffman.encode(qv.codes, 1 << qv.bits, n_streams=64)
            encoded[c] = (ek, ev, qk, qv)
            chunk_bytes[c.t, c.l, 0] = (ek.payload_bytes()
                                        + ev.payload_bytes()
                                        + qk.header_bytes()
                                        + qv.header_bytes())

        # measured chunk stats drive the scheduler (real bytes; active
        # blocks from the block-importance mask on the real q/k; real
        # code-plane entropy feeds the bit allocator's saliency)
        wl = WorkloadChunks(
            n_t=n_t, n_l=n_l, n_h=1, active_blocks=active,
            entropy_bits=ent, chunk_bytes=chunk_bytes,
            head_pattern=np.zeros((n_l, 1), np.int64),
            context_len=s, chunk_tokens=ct)
        cid = self._next_id
        self._next_id += 1
        self.contexts[cid] = StoredContext(
            tokens=tokens, exact_k=k, exact_v=v, encoded=encoded, wl=wl,
            n_chunks=n_t * n_l)
        return cid

    def _measure_active_blocks(self, tokens, n_t, n_l) -> np.ndarray:
        """Per-(t, l) active kv blocks from pooled block scores."""
        from repro.sparse.mask import block_scores, select_blocks
        cfg = self.model.cfg
        ct = self.chunk_tokens
        qb = min(self.spcfg.q_block, ct)
        kb = min(self.spcfg.kv_block, ct)
        # use embeddings as a cheap q/k surrogate at serving time
        emb = np.asarray(
            jnp.take(self.params["emb"], jnp.asarray(tokens), axis=0),
            np.float32)[0]                                    # (S, d)
        x = emb[None]                                         # (1, S, d)
        sc = block_scores(jnp.asarray(x), jnp.asarray(x), q_block=qb,
                          kv_block=kb, causal=True)
        _, cnt = select_blocks(sc, mass=self.spcfg.attention_mass,
                               q_block=qb, kv_block=kb)
        cnt = np.asarray(cnt[0], np.float64)                  # (n_qb,)
        rows_per_chunk = ct // qb
        per_t = cnt.reshape(n_t, rows_per_chunk).sum(axis=1)
        out = np.broadcast_to(per_t[:, None, None],
                              (n_t, n_l, 1)).copy()
        # deeper layers tend denser (observed in the measurement study)
        depth = np.linspace(0.8, 1.2, n_l)[None, :, None]
        return out * depth

    # ---------------- edge side ----------------
    def load_context(self, cid: int, *, policy: str = "sparkv",
                     util: Optional[float] = None, seed: Optional[int] = None):
        """Run the loading pipeline; returns (cache jnp, PipelineResult),
        the cache on the device."""
        st = self.contexts[cid]
        cfg = self.model.cfg
        spcfg = self.spcfg
        u = self.utilization() if util is None else util
        net = NETWORKS[self.network]
        with TraceAnnotation("sparkv.load.plan") as span:
            res = B.PIPELINES[policy](cfg, st.wl, self.profile, net, spcfg,
                                      util=u, seed=seed or self.seed)
            eng = res.engine
            span.set_metadata(streamed=eng.n_streamed,
                              computed=eng.n_computed,
                              migrations=getattr(eng, "n_migrations", 0))
        # concrete assembly
        with TraceAnnotation("sparkv.load.copy_exact",
                             nbytes=st.exact_k.nbytes + st.exact_v.nbytes):
            k = st.exact_k.copy()
            v = st.exact_v.copy()
        ct = self.chunk_tokens
        streamed = sorted(getattr(eng, "streamed_set", set()))
        qts = _entropy_decode([st.encoded[c] for c in streamed])
        if qts:
            # one launch over every streamed plane, whatever its width
            rows = sum(q.scales.shape[0] for q in qts)
            group = qts[0].group
            # up: uint8 codes and a float32 scale and zero per group row;
            # down: float32 values
            with TraceAnnotation("sparkv.load.dequant",
                                 h2d_bytes=rows * (group + 8),
                                 d2h_bytes=rows * group * 4):
                outs = dequantize_chunks(qts, interpret=self.interpret,
                                         out_dtype=jnp.float32)
            with TraceAnnotation("sparkv.load.scatter"):
                for c, kd, vd in zip(streamed, outs[0::2], outs[1::2]):
                    k[c.l, 0, c.t * ct:(c.t + 1) * ct] = kd
                    v[c.l, 0, c.t * ct:(c.t + 1) * ct] = vd
        # the host casts to bfloat16, then uploads
        with TraceAnnotation("sparkv.load.upload",
                             h2d_bytes=(k.size + v.size) * 2):
            cache = {"k": jnp.asarray(k, jnp.bfloat16),
                     "v": jnp.asarray(v, jnp.bfloat16)}
            jax.block_until_ready(cache)
        return cache, res

    def generate(self, cid: int, prompt: np.ndarray, max_new: int = 8,
                 *, policy: str = "sparkv", compare_exact: bool = True,
                 seed: Optional[int] = None) -> ServeResult:
        """Serve one request: load context via `policy`, feed the prompt,
        decode max_new tokens greedily; quality vs the exact cache."""
        t_wall = time.perf_counter()
        self.active_requests += 1
        self._n_requests += 1
        try:
            with TraceAnnotation("sparkv.request", request=self._n_requests,
                                 policy=policy):
                st = self.contexts[cid]
                with TraceAnnotation("sparkv.load"):
                    t_load = time.perf_counter()
                    cache, res = self.load_context(cid, policy=policy,
                                                   seed=seed)
                    t_loaded = time.perf_counter()
                # _decode ends in a host read of the last logits
                toks, logits_seq = self._decode(st, cache, prompt, max_new)
                t_decoded = time.perf_counter()
                t_first = self._first_token_at
                if compare_exact:
                    with TraceAnnotation("sparkv.compare_exact"):
                        exact_cache = {
                            "k": jnp.asarray(st.exact_k, jnp.bfloat16),
                            "v": jnp.asarray(st.exact_v, jnp.bfloat16)}
                        etoks, elogits = self._decode(st, exact_cache,
                                                      prompt, max_new)
                    agree = float(np.mean(toks == etoks))
                    kl = float(np.mean([_kl(e, a) for e, a
                                        in zip(elogits, logits_seq)]))
                else:
                    agree, kl = 1.0, 0.0
                eng = res.engine
                return ServeResult(
                    ttft_s=res.ttft_s, energy_j=res.energy_j, tokens=toks,
                    top1_agreement=agree, mean_kl=kl,
                    n_streamed=eng.n_streamed, n_computed=eng.n_computed,
                    wall_s=time.perf_counter() - t_wall,
                    load_wall_s=t_loaded - t_load,
                    decode_wall_s=t_decoded - t_loaded,
                    first_token_s=t_first - t_wall)
        finally:
            self.active_requests -= 1

    def serve_fleet(self, jobs: list[tuple[int, float, str]], *,
                    closed_loop: bool = True, static_util: float = 0.0,
                    max_concurrency: Optional[int] = None,
                    link=None, run_queue=None, policy_fn=None,
                    slo=None, deadline_s: Optional[float] = None,
                    max_new_tokens: int = 0, decode=None,
                    tpot_slo_s: Optional[float] = None,
                    bw_seed: int = 991):
        """Serve many registered contexts concurrently on one clock.

        jobs: (cid, arrival_s, policy) triples over contexts previously
        created with register_context(). Timing/energy come from the
        multi-request cluster (link topology + device servers); KV
        content for any request can still be assembled afterwards with
        load_context(). Pass a ``repro.core.costs.RunQueueModel`` as
        ``run_queue`` to serve compute through the explicit
        FIFO/WFQ/SRPT device queue, and/or a ``policy_fn`` (e.g.
        ``repro.serving.cluster.telemetry_policy``) to pick policies from
        live telemetry at admission. An ``repro.serving.slo.SLOPolicy``
        as ``slo`` (with ``deadline_s`` applied to every job) arms
        deadline-aware admission: downgrade-or-shed on predicted TTFT
        violation. ``max_new_tokens > 0`` keeps every request alive past
        its first token: responses decode through the per-device
        continuous batch (tune it with a
        ``repro.serving.decode.DecodeConfig`` as ``decode``; an optional
        ``tpot_slo_s`` arms per-token admission under ``slo``). Returns
        a FleetReport.
        """
        from repro.serving.cluster import RequestSpec, ServingCluster
        specs = []
        for i, (cid, arrival_s, policy) in enumerate(jobs):
            st = self.contexts[cid]
            specs.append(RequestSpec(
                arrival_s=arrival_s, context_len=st.wl.context_len,
                policy=policy, seed=i, wl=st.wl, deadline_s=deadline_s,
                max_new_tokens=max_new_tokens, tpot_slo_s=tpot_slo_s))
        cluster = ServingCluster(
            self.model.cfg, self.spcfg, self.profile, self.network,
            capacity=self.capacity,
            max_concurrency=max_concurrency or self.capacity,
            closed_loop=closed_loop, static_util=static_util,
            link=link, run_queue=run_queue, policy_fn=policy_fn,
            slo=slo, decode=decode, bw_seed=bw_seed, seed=self.seed)
        return cluster.run(specs)

    def _decode(self, st: StoredContext, cache, prompt, max_new):
        """Feed the prompt, then decode max_new tokens greedily; returns
        the answer tokens and their logits. Sets ``_first_token_at``, the
        host clock once the first answer token's argmax has returned."""
        cfg = self.model.cfg
        s = st.tokens.shape[1]
        with TraceAnnotation("sparkv.decode"):
            # context cache is exactly s (read-only); prompt + generated
            # tokens go to the replicated decode tail buffer
            with TraceAnnotation("sparkv.decode.init_cache"):
                full = self.model.init_cache(1, s)
                full["k"] = cache["k"][:, :, :s].astype(full["k"].dtype)
                full["v"] = cache["v"][:, :, :s].astype(full["v"].dtype)
            toks = []
            logits_list = []
            cur = None
            pos = s
            n_q = len(prompt)
            feed = list(prompt) + [None] * max_new
            for i, tok in enumerate(feed):
                if tok is None:
                    tok = cur
                kind = "question" if i < n_q else "answer"
                with TraceAnnotation(f"sparkv.step.{kind}", pos=pos) as span:
                    logits, full = self._decode_step(
                        self.params, full, jnp.asarray([tok], jnp.int32),
                        jnp.int32(pos))
                    row = logits[0]
                    lf = np.asarray(row, np.float32)
                    cur = int(lf[:cfg.vocab_size].argmax())
                    span.set_metadata(d2h_bytes=row.nbytes)
                if i == n_q - 1:
                    self._first_token_at = time.perf_counter()
                pos += 1
                toks.append(cur)
                logits_list.append(lf)
        return np.asarray(toks[n_q:]), logits_list[n_q:]


def _entropy_decode(stored: list) -> list[QuantizedTensor]:
    """The K and V plane codes of each stored chunk in turn, Huffman-decoded
    from their bitstreams in one lockstep loop, each checked against the
    codes it was encoded from."""
    if not stored:
        return []
    encs = [enc for ek, ev, _, _ in stored for enc in (ek, ev)]
    qts = [qt for _, _, qk, qv in stored for qt in (qk, qv)]
    lanes, steps, bits = huffman.lockstep_shape(encs)
    with TraceAnnotation("sparkv.load.entropy_decode",
                         values=sum(e.n_total for e in encs),
                         nbytes=sum(e.payload_bytes() for e in encs),
                         planes=len(encs), lanes=lanes, steps=steps,
                         table_bits=bits):
        codes = huffman.decode_many(encs)
        for c, qt in zip(codes, qts):
            assert np.array_equal(c, qt.codes), "bitstream corruption"
    return [dataclasses.replace(qt, codes=c.astype(np.uint8))
            for c, qt in zip(codes, qts)]


def _kl(p_logits: np.ndarray, q_logits: np.ndarray) -> float:
    p = p_logits - p_logits.max()
    q = q_logits - q_logits.max()
    lp = p - np.log(np.exp(p).sum())
    lq = q - np.log(np.exp(q).sum())
    return float(np.sum(np.exp(lp) * (lp - lq)))
