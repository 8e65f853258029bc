"""The program's own spans (``repro.core.tracing``) over the window's
points, for the per-layer readers of the turnaround cell.

After the warm-up sweep, the window's points are the last ``point`` spans
the process records: nothing after the window runs a point.  Each helper
returns ``None`` where the program records no such spans (a program from
before them), where it holds fewer ``point`` spans than the window ran, or
where its store has wrapped past the window.
"""

from __future__ import annotations


def window(obs):
    """The subtree of each of the window's ``point`` spans, or ``None``."""
    n = obs.get("points")
    if not n:
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    try:
        return tracing.spans("point", last=n)
    except LookupError:
        return None


def total_ms(obs, *names):
    """Total ms per point of the spans called ``names``."""
    trees = window(obs)
    if trees is None:
        return None
    ns = sum(t.names[k].total_ns for t in trees for k in names if k in t.names)
    return ns / 1e6 / len(trees)


def self_ms(obs, name):
    """Self time per point, in ms, of the spans called ``name``."""
    trees = window(obs)
    if trees is None:
        return None
    ns = sum(t.names[name].self_ns for t in trees if name in t.names)
    return ns / 1e6 / len(trees)


def counter(obs, name):
    """The counter ``name`` summed over the window's points, and the
    number of points, or ``None``."""
    trees = window(obs)
    if trees is None:
        return None
    return sum(t.counters.get(name, 0) for t in trees), len(trees)
