"""Canonical Huffman codec with SIMD-style interleaved multi-stream decode.

Real bitstreams (this is what goes over the simulated wire, and roundtrip
exactness is tested). Sequential Huffman decode is unvectorizable, so —
like production entropy coders (interleaved rANS) — we split symbols into S
independent streams. `decode_many` decodes every stream of several
encoded planes in lockstep with numpy gathers: the loop runs
max-symbols-per-stream passes, each vectorized across all planes' streams.

Max code length is capped at MAX_LEN (table-driven decode, at most 2^16
entries); if the unrestricted Huffman tree exceeds it, counts are
flattened toward uniform until it fits (tiny rate loss, recorded by the
caller via actual encoded size).
"""
from __future__ import annotations

import dataclasses
import heapq

import numpy as np

MAX_LEN = 16


def _code_lengths(counts: np.ndarray) -> np.ndarray:
    """Huffman code lengths per symbol (0 for absent symbols)."""
    n = len(counts)
    active = [int(s) for s in np.nonzero(counts)[0]]
    if not active:
        return np.zeros(n, np.int32)
    if len(active) == 1:
        out = np.zeros(n, np.int32)
        out[active[0]] = 1
        return out
    flat = counts.astype(np.float64)
    for _ in range(32):
        heap = [(float(flat[s]), i, (s,)) for i, s in enumerate(active)]
        heapq.heapify(heap)
        uid = len(heap)
        depth = {s: 0 for s in active}
        while len(heap) > 1:
            c1, _, s1 = heapq.heappop(heap)
            c2, _, s2 = heapq.heappop(heap)
            for s in s1 + s2:
                depth[s] += 1
            heapq.heappush(heap, (c1 + c2, uid, s1 + s2))
            uid += 1
        lens = np.zeros(n, np.int32)
        for s, d in depth.items():
            lens[s] = d
        if lens.max() <= MAX_LEN:
            return lens
        # flatten the distribution and retry
        flat = np.sqrt(flat) * flat.sum() / np.maximum(
            np.sqrt(flat).sum(), 1e-9)
        flat[np.asarray(active)] = np.maximum(flat[np.asarray(active)], 1.0)
    raise RuntimeError("could not limit Huffman code length")


def _canonical_codes(lens: np.ndarray) -> np.ndarray:
    """Canonical code values (uint16) from lengths."""
    n = len(lens)
    codes = np.zeros(n, np.uint16)
    code = 0
    prev_len = 0
    order = sorted((l, s) for s, l in enumerate(lens) if l > 0)
    for l, s in order:
        code <<= (l - prev_len)
        codes[s] = code
        code += 1
        prev_len = l
    return codes


@dataclasses.dataclass
class HuffmanCode:
    lengths: np.ndarray    # (n_symbols,) int32
    codes: np.ndarray      # (n_symbols,) uint16

    @classmethod
    def from_counts(cls, counts: np.ndarray) -> "HuffmanCode":
        lens = _code_lengths(np.asarray(counts))
        return cls(lengths=lens, codes=_canonical_codes(lens))

    def table_bytes(self) -> int:
        return len(self.lengths)  # one length byte per symbol (canonical)

    def decode_table(self, bits: int = MAX_LEN):
        """(symbol, length) uint16 arrays indexed by the next ``bits`` bits
        of the stream; ``bits`` is at least the longest code length."""
        sym = np.zeros(1 << bits, np.uint16)
        ln = np.zeros(1 << bits, np.uint16)
        for s, l in enumerate(self.lengths):
            l = int(l)
            if l == 0:
                continue
            prefix = int(self.codes[s]) << (bits - l)
            span = 1 << (bits - l)
            sym[prefix:prefix + span] = s
            ln[prefix:prefix + span] = l
        return sym, ln


@dataclasses.dataclass
class EncodedChunk:
    streams: np.ndarray        # (S, max_bytes) uint8
    bit_lengths: np.ndarray    # (S,) int64
    n_per_stream: np.ndarray   # (S,) int64 symbol counts
    n_symbols_alphabet: int
    code: HuffmanCode
    n_total: int

    def payload_bytes(self) -> int:
        return int(np.sum((self.bit_lengths + 7) // 8)) \
            + self.code.table_bytes() + 4 * len(self.bit_lengths)


def encode(symbols: np.ndarray, n_alphabet: int,
           n_streams: int = 64) -> EncodedChunk:
    symbols = np.asarray(symbols, np.uint16).reshape(-1)
    n = len(symbols)
    counts = np.bincount(symbols, minlength=n_alphabet)
    code = HuffmanCode.from_counts(counts)

    s = min(n_streams, max(1, n))
    per = -(-n // s)
    pad = s * per - n
    syms = np.concatenate([symbols, np.zeros(pad, np.uint16)])
    syms = syms.reshape(s, per)
    n_per = np.full(s, per, np.int64)
    if pad:
        n_per[-1] -= 0  # padding symbols live in the last rows
        full_rows = n // per
        n_per[:] = per
        n_per[full_rows] = n - full_rows * per if full_rows < s else per
        n_per[full_rows + 1:] = 0

    lens = code.lengths[syms]                                  # (s, per)
    codes = code.codes[syms].astype(np.uint32)

    # valid mask (ignore padding symbols)
    valid = np.arange(per)[None, :] < n_per[:, None]
    lens = np.where(valid, lens, 0)

    bit_lengths = lens.sum(axis=1).astype(np.int64)
    max_bits = int(bit_lengths.max()) if s else 0
    max_bytes = (max_bits + 7) // 8 + 4                        # decode slack
    out = np.zeros((s, max_bytes * 8), np.uint8)

    # vectorized bit placement per stream
    ends = np.cumsum(lens, axis=1)
    starts = ends - lens
    total = int(lens.sum())
    if total:
        row = np.repeat(np.arange(s)[:, None].repeat(per, 1).reshape(-1),
                        lens.reshape(-1))
        off = np.repeat(starts.reshape(-1), lens.reshape(-1))
        intra = (np.arange(total)
                 - np.repeat(np.cumsum(lens.reshape(-1))
                             - lens.reshape(-1), lens.reshape(-1)))
        l_rep = np.repeat(lens.reshape(-1), lens.reshape(-1))
        c_rep = np.repeat(codes.reshape(-1), lens.reshape(-1))
        bits = (c_rep >> (l_rep - 1 - intra)) & 1
        out[row, off + intra] = bits.astype(np.uint8)

    streams = np.packbits(out, axis=1)
    return EncodedChunk(streams=streams, bit_lengths=bit_lengths,
                        n_per_stream=n_per, n_symbols_alphabet=n_alphabet,
                        code=code, n_total=n)


def decode(enc: EncodedChunk) -> np.ndarray:
    """One plane's symbols (uint16), as ``encode`` took them."""
    return decode_many([enc])[0]


def lockstep_shape(encs: list[EncodedChunk]) -> tuple[int, int, int]:
    """(lanes, steps, table_bits) of decoding ``encs`` together: one lane
    per stream, one pass per symbol of the longest stream, and tables
    indexed by as many bits as the longest code."""
    lanes = sum(e.streams.shape[0] for e in encs)
    steps = max((int(e.n_per_stream.max(initial=0)) for e in encs),
                default=0)
    bits = max((int(e.code.lengths.max(initial=0)) for e in encs),
               default=0)
    return lanes, steps, bits


_TILE = (64, 1024)      # (lanes, passes) per block of the final transpose


def decode_many(encs: list[EncodedChunk]) -> list[np.ndarray]:
    """Decode several encoded planes in one lockstep loop; returns each
    plane's symbols (uint16) as ``encode`` took them.

    Every stream of every plane is one lane. Each pass reads the next
    ``bits`` bits of each lane that has symbols left and looks them up in
    the lane's own plane's table, cut to ``2^bits`` entries. Lanes run
    longest first, so those with symbols left are always a prefix, and a
    lane that has decoded its count reads nothing more; the others read
    only inside their own byte row."""
    if not encs:
        return []
    lanes, steps, bits = lockstep_shape(encs)
    # one int32 entry per window: the symbol, and the code length above it
    table = np.concatenate([
        sym.astype(np.int32) | (ln.astype(np.int32) << 16)
        for sym, ln in (e.code.decode_table(bits) for e in encs)])
    # one byte row per lane, padded to the longest plane plus 4 bytes of
    # slack, then the 24-bit window that starts at each byte
    width = max(e.streams.shape[1] for e in encs) + 4
    rows = np.zeros((lanes, width), np.uint8)
    plane = np.repeat(np.arange(len(encs)),
                      [e.streams.shape[0] for e in encs])
    lo = 0
    for e in encs:
        rows[lo:lo + e.streams.shape[0], :e.streams.shape[1]] = e.streams
        lo += e.streams.shape[0]
    win = rows[:, :-2].astype(np.int32)
    win <<= 8
    win |= rows[:, 1:-1]
    win <<= 8
    win |= rows[:, 2:]
    win = win.reshape(-1)
    # lane state, longest lane first: bit address in ``win``, table offset
    counts = np.concatenate([e.n_per_stream for e in encs])
    order = np.argsort(-counts, kind="stable")
    ends = counts[order]
    addr_t = np.int32 if win.size * 8 < 2 ** 31 else np.int64
    pos = order.astype(addr_t) * ((width - 2) * 8)
    off = (plane[order] << bits).astype(np.int32)
    byte = np.empty(lanes, addr_t)
    shift = np.empty(lanes, np.int32)
    w = np.empty(lanes, np.int32)
    out = np.empty((steps, lanes), np.uint16)
    mask = (1 << bits) - 1
    i = 0
    while i < steps:
        k = int(np.count_nonzero(ends > i))     # lanes with symbols left
        stop = int(ends[k - 1])
        p, b, sh, wk, o = pos[:k], byte[:k], shift[:k], w[:k], off[:k]
        for j in range(i, stop):
            np.right_shift(p, 3, out=b)
            np.take(win, b, out=wk)
            np.bitwise_and(p, 7, out=sh)
            np.subtract(24 - bits, sh, out=sh)
            np.right_shift(wk, sh, out=wk)
            np.bitwise_and(wk, mask, out=wk)
            np.add(wk, o, out=wk)
            # take buffers ``out`` in its default mode, so it may alias
            np.take(table, wk, out=wk)
            out[j, :k] = wk                     # the symbol: low 16 bits
            np.right_shift(wk, 16, out=wk)
            np.add(p, wk, out=p)
        i = stop
    # lanes by rows, in blocks that stay in cache
    by_lane = np.empty((lanes, steps), np.uint16)
    tl, ts = _TILE
    for a in range(0, lanes, tl):
        for c in range(0, steps, ts):
            by_lane[a:a + tl, c:c + ts] = out[c:c + ts, a:a + tl].T
    row = np.empty(lanes, np.intp)
    row[order] = np.arange(lanes)
    res, lo = [], 0
    for e in encs:
        res.append(np.concatenate(
            [by_lane[row[lo + r], :n] for r, n in enumerate(e.n_per_stream)]))
        lo += e.streams.shape[0]
    return res


def entropy_bits(symbols: np.ndarray, n_alphabet: int) -> float:
    counts = np.bincount(np.asarray(symbols, np.int64).reshape(-1),
                         minlength=n_alphabet).astype(np.float64)
    p = counts / max(counts.sum(), 1)
    nz = p[p > 0]
    return float(-(nz * np.log2(nz)).sum())
