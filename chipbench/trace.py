"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-operation device time, and idle gaps labelled with the harness's own
host spans.

Device operations are the events of each ``/device:TPU:<n>`` plane's
``XLA Ops`` line; the modules they ran in are that plane's ``XLA
Modules`` line. Busy time is the union of the operation intervals inside
the window, which is the harness's ``chipbench.window`` span. An
operation is named ``<module>/<instruction>``, and only operations that
enclose no other (not a ``while`` around its body) count towards the
seconds per operation. A gap in which no operation runs is labelled with
the innermost ``chipbench.*`` span that covers its middle on the host.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                     # mean over the chips
    op_s: dict                        # op name -> device seconds
    module_s: dict                    # module name -> device seconds
    gaps: list                        # (label, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_seconds(self, needle: str) -> float:
        """Device seconds of the modules whose name holds ``needle``."""
        return sum(s for n, s in self.module_s.items() if needle in n)

    def breakdown(self, k: int = 10) -> dict:
        by_label = defaultdict(float)
        for label, s in self.gaps:
            by_label[label] += s
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]
        idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in idle]}


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _label(spans, t):
    """Innermost span (shortest) that covers time t, or "none"."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "none"


def reduce(devices: dict, spans: list, window=None) -> Summary:
    """devices: {device: {"ops": [(name, start_ns, end_ns, leaf)],
    "modules": [(name, start_ns, end_ns)]}}; spans: host [(name,
    start_ns, end_ns)]. The window is the ``chipbench.window`` span unless
    given."""
    if window is None:
        win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if not win:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        window = (min(s for s, _ in win), max(e for _, e in win))
    lo, hi = window
    op_s, module_s, gaps = defaultdict(float), defaultdict(float), []
    busy = 0.0
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    for dev in devices.values():
        ivs = []
        for name, s, e, leaf in dev["ops"]:
            c = _clip([(s, e)], lo, hi)
            if c:
                if leaf:
                    op_s[name] += (c[0][1] - c[0][0]) * 1e-9
                ivs.append(c[0])
        for name, s, e in dev.get("modules", []):
            c = _clip([(s, e)], lo, hi)
            if c:
                module_s[name] += (c[0][1] - c[0][0]) * 1e-9
        merged = union(ivs)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_label(inner, (a + b) / 2), (b - a) * 1e-9))
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy / n,
                   op_s=dict(op_s),
                   module_s=dict(module_s), gaps=gaps)


def _base(name: str) -> str:
    """A module's name without its program id: ``jit_f(12)`` -> ``jit_f``."""
    return name.split("(")[0]


def _instruction(name: str) -> str:
    """An HLO instruction's name: ``%fusion.5 = bf16[..] fusion(..)`` ->
    ``fusion.5``."""
    return name.split(" = ")[0].lstrip("%")


def _named_ops(ops: list, modules: list) -> list:
    """(``<module>/<instruction>``, start, end, leaf) for each operation,
    leaf false where the next operation starts inside it."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, (name, s, e) in enumerate(ops):
        j = bisect.bisect_right(starts, s) - 1
        mod = modules[j][0] if j >= 0 and modules[j][2] >= s else "?"
        leaf = i + 1 == len(ops) or ops[i + 1][1] >= e
        out.append((f"{mod}/{_instruction(name)}", s, e, leaf))
    return out


def read_profile(pd) -> tuple[dict, list]:
    """(devices, host spans) of a ``jax.profiler.ProfileData``."""
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            lines = {line.name: [(ev.name, ev.start_ns, ev.end_ns)
                                 for ev in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            modules = [(_base(n), s, e)
                       for n, s, e in lines.get(MODULES_LINE, [])]
            devices[plane.name] = {
                "ops": _named_ops(lines.get(OPS_LINE, []), modules),
                "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return devices, spans


def load(log_dir: str) -> Summary:
    """The summary of the one ``.xplane.pb`` under a profiler log dir."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    devices, spans = read_profile(ProfileData.from_file(paths[0]))
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    return reduce(devices, spans)
