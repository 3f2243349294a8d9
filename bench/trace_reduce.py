"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
per-operation device time and the longest idle gaps.

Device operations are the events of each ``/device:TPU:<k>`` plane's
``XLA Ops`` line.  A trace with no device plane (a CPU run) falls back to
host events that carry an ``hlo_op`` stat, grouped by ``device_ordinal`` —
that is how this reduction is tested without a chip.  The window is the
benchmark's own ``bench:window`` annotation; every device interval is
clipped to it, and each idle gap is labelled by the benchmark's innermost
``bench:`` annotation that covers the gap's middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import warnings
from typing import Dict, List, Optional, Tuple

WINDOW = "bench:window"
PREFIX = "bench:"
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# Control-flow ops span the ops of their bodies on the same trace line: they
# count toward busy time (a union) but not in the per-op breakdown.
_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: Dict[int, float]                    # device → busy seconds
    op_s: Dict[str, float]                      # op name → seconds, all devices
    gaps: List[Tuple[str, float]]               # (host label, seconds), longest first

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s

    def time_of(self, prefix: str) -> float:
        """Device seconds of every op whose name starts with ``prefix``."""
        return sum(s for n, s in self.op_s.items() if n.startswith(prefix))

    def by_kind(self, k: int = 12) -> List[Tuple[str, float]]:
        """Device seconds by op kind (the name without its ``.<n>``)."""
        kinds: Dict[str, float] = {}
        for n, s in self.op_s.items():
            if not _CONTAINER.match(n):
                kind = re.sub(r"\.\d+$", "", n)
                kinds[kind] = kinds.get(kind, 0.0) + s
        return sorted(kinds.items(), key=lambda kv: -kv[1])[:k]

    def breakdown(self, k: int = 10) -> Dict[str, list]:
        ops = sorted(((n, s) for n, s in self.op_s.items()
                      if not _CONTAINER.match(n)), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:k]]}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stats(ev) -> dict:
    # The profiler's stats type warns once, as it is built, that it has no
    # __module__: noise for every caller.
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def op_name(event_name: str) -> str:
    """The HLO instruction's name: a TPU trace names each op event by the
    instruction's text, ``%name = shape op(...)``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def device_ops(profile) -> Dict[int, List[Tuple[str, float, float]]]:
    """device → [(op name, start ns, end ns)]."""
    out: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in profile.planes:
        m = _TPU_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                out.setdefault(int(m.group(1)), []).extend(
                    (op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events)
    if out:
        return out
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                st = _stats(ev)
                if "hlo_op" in st:
                    out.setdefault(int(st.get("device_ordinal", 0)), []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def host_spans(profile) -> List[Tuple[str, float, float]]:
    """The benchmark's own annotations: [(name, start ns, end ns)]."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events if ev.name.startswith(PREFIX))
    return out


def union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Sorted disjoint union of ``intervals`` clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _label(spans: List[Tuple[str, float, float]], t: float) -> str:
    """The innermost benchmark annotation covering time ``t``."""
    best: Optional[Tuple[str, float, float]] = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0][len(PREFIX):] if best else "outside"


def summarize(profile) -> Summary:
    spans = host_spans(profile)
    windows = [(a, b) for n, a, b in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    lo, hi = windows[0]
    ops = device_ops(profile)
    if not ops:
        raise ValueError("the trace holds no device operation")
    busy_s, op_s, all_gaps = {}, {}, []
    for dev, evs in sorted(ops.items()):
        busy = union([(a, b) for _, a, b in evs], lo, hi)
        busy_s[dev] = sum(b - a for a, b in busy) / 1e9
        for name, a, b in evs:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                op_s[name] = op_s.get(name, 0.0) + d / 1e9
        all_gaps += [(_label(spans, (a + b) / 2), (b - a) / 1e9)
                     for a, b in gaps(busy, lo, hi)]
    all_gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_s, op_s=op_s,
                   gaps=all_gaps)


def load(trace_dir: str) -> Summary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(find_xplane(trace_dir)))
