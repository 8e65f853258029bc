"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The harness marks the measured window with the host annotation
``bench.window`` and each host activity with ``bench.<name>``.  From the
device planes (``/device:TPU:<n>``, line ``XLA Ops``) this module takes,
per chip and inside the window:

- busy time: the union of the intervals in which an operation ran;
- collective time: the union of the collective operations' intervals;
- time per operation name, for the breakdown;
- idle gaps, each named by the innermost ``bench.*`` host span that covers
  its midpoint (``other`` when none does).

Every number is averaged over the chips that ran an operation.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Z]+:\d+$")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all"
    r"|collective-broadcast|send|recv)"
)


@dataclass
class TraceSummary:
    window_s: float
    busy_s: list  # per chip
    collective_s: list  # per chip
    ops: dict = field(default_factory=dict)  # op name -> seconds per chip
    gaps: dict = field(default_factory=dict)  # host span -> idle s per chip

    @property
    def n_chips(self) -> int:
        return len(self.busy_s)

    @property
    def mean_busy_s(self) -> float:
        return float(np.mean(self.busy_s))

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.mean_busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> dict:
        def rank(d):
            largest = sorted(d.items(), key=lambda kv: -kv[1])[:top]
            return [[k, float(v)] for k, v in largest]

        return {"device_ops": rank(self.ops), "idle_gaps": rank(self.gaps)}


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}: {found}")
    return found[0]


def op_name(event_name: str) -> str:
    """``fusion.3`` from a device event named with its whole HLO
    instruction (``%fusion.3 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    names, starts, ends = [], [], []
    for ev in line.events:
        names.append(op_name(ev.name))
        starts.append(ev.start_ns)
        ends.append(ev.end_ns)
    return names, np.asarray(starts, float), np.asarray(ends, float)


def merge(starts: np.ndarray, ends: np.ndarray) -> tuple:
    """Union of intervals as sorted, disjoint ``(starts, ends)``."""
    if not len(starts):
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, len(s) - 1]
    return s[first], reach[last]


def _host_spans(pd) -> tuple:
    """``(window, spans)``: the window interval and every other ``bench.*``
    host span as ``(name, start, end)``."""
    window, spans = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                if ev.name == WINDOW:
                    if window is not None:
                        raise ValueError("more than one bench.window span")
                    window = (ev.start_ns, ev.end_ns)
                else:
                    name = ev.name[len(SPAN_PREFIX) :]
                    spans.append((name, ev.start_ns, ev.end_ns))
    if window is None:
        raise ValueError("no bench.window span in the trace")
    return window, spans


def _name_gaps(gs, ge, spans) -> dict:
    out: dict = {}
    if not len(gs):
        return out
    mid = (gs + ge) / 2
    best = np.full(len(gs), np.inf)
    who = np.full(len(gs), "other", dtype=object)
    for name, s, e in spans:
        inside = (mid >= s) & (mid < e) & ((e - s) < best)
        best[inside] = e - s
        who[inside] = name
    for name in set(who):
        out[name] = float(np.sum((ge - gs)[who == name])) / 1e9
    return out


def summarize(pd) -> TraceSummary:
    """Reduce a loaded ``ProfileData`` to a :class:`TraceSummary`."""
    (w0, w1), spans = _host_spans(pd)
    busy, coll, ops, gaps = [], [], {}, {}
    for plane in pd.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not lines:
            continue
        names, s, e = _events(lines[0])
        inside = (e > w0) & (s < w1)
        if not inside.any():
            continue
        names = [n for n, k in zip(names, inside) if k]
        s, e = np.clip(s[inside], w0, w1), np.clip(e[inside], w0, w1)
        ms, me = merge(s, e)
        busy.append(float(np.sum(me - ms)) / 1e9)
        is_coll = np.array([bool(_COLLECTIVE.match(n)) for n in names], bool)
        cs, ce = merge(s[is_coll], e[is_coll])
        coll.append(float(np.sum(ce - cs)) / 1e9)
        for n, d in zip(names, e - s):
            ops[n] = ops.get(n, 0.0) + d / 1e9
        gs = np.r_[w0, me]
        ge = np.r_[ms, w1]
        keep = ge > gs
        for name, sec in _name_gaps(gs[keep], ge[keep], spans).items():
            gaps[name] = gaps.get(name, 0.0) + sec
    if not busy:
        raise ValueError("no device operation ran inside the window")
    n = len(busy)
    return TraceSummary(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy,
        collective_s=coll,
        ops={k: v / n for k, v in ops.items()},
        gaps={k: v / n for k, v in gaps.items()},
    )


def load(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return summarize(ProfileData.from_file(path))
