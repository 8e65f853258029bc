"""Spans and counters at the layer boundaries of the spec-to-Frame path.

``span(name)`` times a block of host code.  It enters
``jax.profiler.TraceAnnotation("repro.<name>")``, so that a profiler trace
shows the span on its host plane, on the clock the device planes are
synced to, and it records one span in an in-memory store: its name, start
and end (``time.perf_counter_ns``), the span it opened inside (its parent,
from a per-thread stack) and the outermost span of that stack (its root).
``count(name, n)`` adds ``n`` to a counter of the innermost span open on
the calling thread, so that ratios are taken where the work happens; a
count with no span open is dropped.  ``spans(name, last=n)`` reads the
last ``n`` spans called ``name`` back, each with its subtree summed by
name (count, total and self time) and the counters recorded in it.

Recording is always on.  The store is a ring of :data:`CAPACITY` spans,
allocated when the module is imported: a span costs a few list and buffer
writes and no I/O, and past the capacity the oldest spans are overwritten.
``spans`` raises :class:`Wrapped` when the spans asked for may have been
overwritten, rather than return part of them.

On import the module registers one ``jax.monitoring`` listener that counts
``compiles`` on the span open on the compiling thread: one for each
backend compile event, which JAX records once for a fresh XLA compile and
once for a load from the persistent compilation cache.

Spans recorded in a process-pool worker stay in that worker's store.
"""

from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass

import jax
import numpy as np

#: Spans the store holds before it wraps: about 1,500 points (ten times
#: the turnaround rate of 2.8 points/s, over a 51 s window) at up to 200
#: spans a point is 300,000 spans, below 2**19.  56 bytes a span (six
#: int64 fields and a counter slot): 28 MiB in all.
CAPACITY = 1 << 19

#: Prefix of every span's name in a profiler trace.
PREFIX = "repro."

_FIELDS = 6  # seq, name id, start ns, end ns (-1 while open), parent, root
#: Recorded around ``compile_or_get_cached``: a fresh compile or a cache load.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_clock = time.perf_counter_ns


class Wrapped(LookupError):
    """The store overwrote spans that an asked-for window may need."""


@dataclass(frozen=True)
class Layer:
    """The spans of one name inside a subtree."""

    count: int
    total_ns: int
    self_ns: int  # total minus what their child spans cover
    parents: dict  # parent span name -> how many of these spans it holds


@dataclass(frozen=True)
class Subtree:
    """One span and everything recorded under it."""

    seq: int  # the span's id; ids grow in the order spans open
    root: int  # the id of the outermost span open when it opened
    start_ns: int
    end_ns: int
    names: dict  # span name -> Layer, the span itself included
    counters: dict  # counter name -> sum over the subtree


class _Stack(threading.local):
    def __init__(self):
        self.open = []  # [seq, counters or None, root] per open span


class Store:
    """A ring of ``capacity`` spans and the per-thread stacks of open ones."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self._buf = np.zeros(capacity * _FIELDS, np.int64)
        self._mv = memoryview(self._buf)
        self._counters = [None] * capacity
        self._seq = itertools.count(1)  # 0 marks a slot never written
        self._ids: dict = {}  # name -> (id, profiler label)
        self._names: list = []
        self._lock = threading.Lock()
        self._stack = _Stack()

    def _name(self, name: str) -> tuple:
        hit = self._ids.get(name)
        if hit is None:
            with self._lock:
                hit = self._ids.get(name)
                if hit is None:
                    hit = (len(self._names), PREFIX + name)
                    self._names.append(name)
                    self._ids[name] = hit
        return hit

    def open(self, nid: int) -> list:
        stack = self._stack.open
        seq = next(self._seq)
        if stack:
            parent, root = stack[-1][0], stack[-1][2]
        else:
            parent, root = 0, seq
        k = (seq % self.capacity) * _FIELDS
        mv = self._mv
        mv[k] = seq
        mv[k + 1] = nid
        mv[k + 3] = -1
        mv[k + 4] = parent
        mv[k + 5] = root
        frame = [seq, None, root]
        stack.append(frame)
        mv[k + 2] = _clock()
        return frame

    def close(self, frame: list) -> None:
        end = _clock()
        self._stack.open.pop()
        seq = frame[0]
        slot = seq % self.capacity
        k = slot * _FIELDS
        if self._mv[k] == seq:  # else a newer span took the slot: drop it
            self._mv[k + 3] = end
            self._counters[slot] = frame[1]

    def spans(self, name: str, last: int) -> list:
        """The last ``last`` closed spans called ``name``, oldest first, as
        :class:`Subtree`.  Raises :class:`Wrapped` if fewer are held and
        the store has overwritten spans, else ``LookupError`` if fewer were
        recorded.  A span asked for inside another of the same name heads
        its own subtree, outside the other's."""
        if last < 1:
            raise ValueError(f"last must be at least 1, not {last}")
        recs = self._buf.reshape(self.capacity, _FIELDS)
        seqs = recs[:, 0]
        hi = int(seqs.max()) + 1  # one past the newest span opened
        keep = (seqs >= max(1, hi - self.capacity)) & (recs[:, 3] >= 0)
        rows = recs[keep]
        rows = rows[np.argsort(rows[:, 0])]
        hit = self._ids.get(name)
        pick = np.flatnonzero(rows[:, 1] == hit[0]) if hit else np.zeros(0, int)
        if len(pick) < last:
            held = f"{len(pick)} spans called {name!r} are held, {last} asked for"
            if hi > self.capacity + 1:
                kept = f"the store kept only its last {self.capacity} spans"
                raise Wrapped(f"{held}; {kept}")
            raise LookupError(held)
        pick = pick[len(pick) - last :]
        sub = rows[pick[0] :]
        m = len(sub)
        seq = sub[:, 0]
        ppos = np.searchsorted(seq, sub[:, 4])
        inside = (ppos < m) & (seq[np.minimum(ppos, m - 1)] == sub[:, 4])
        ppos = np.where(inside, ppos, -1)

        lab = np.full(m, -1)
        lab[pick - pick[0]] = np.arange(last)
        anc = ppos.copy()
        todo = np.flatnonzero((lab < 0) & (anc >= 0))
        while len(todo):  # one pass per level of nesting
            up = anc[todo]
            got = lab[up]
            lab[todo] = got
            anc[todo] = np.where(got >= 0, -1, ppos[up])
            todo = todo[anc[todo] >= 0]

        dur = sub[:, 3] - sub[:, 2]
        under = np.flatnonzero((ppos >= 0) & (lab >= 0))
        under = under[lab[ppos[under]] == lab[under]]
        covered = np.zeros(m, np.int64)
        np.add.at(covered, ppos[under], dur[under])
        parent_of = np.full(m, -1)
        parent_of[under] = sub[ppos[under], 1]

        names = [dict() for _ in range(last)]
        counters = [dict() for _ in range(last)]
        for i in np.flatnonzero(lab >= 0):
            k = lab[i]
            nm = self._names[sub[i, 1]]
            acc = names[k].setdefault(nm, [0, 0, 0, {}])
            acc[0] += 1
            acc[1] += int(dur[i])
            acc[2] += int(dur[i] - covered[i])
            if parent_of[i] >= 0:
                pn = self._names[parent_of[i]]
                acc[3][pn] = acc[3].get(pn, 0) + 1
            c = self._counters[seq[i] % self.capacity]
            if c:
                for cn, v in c.items():
                    counters[k][cn] = counters[k].get(cn, 0) + v
        return [
            Subtree(
                seq=int(sub[p, 0]),
                root=int(sub[p, 5]),
                start_ns=int(sub[p, 2]),
                end_ns=int(sub[p, 3]),
                names={nm: Layer(*acc) for nm, acc in names[k].items()},
                counters=counters[k],
            )
            for k, p in enumerate(pick - pick[0])
        ]


_store = Store()


class span:
    """``with span(name):`` records the block as a span called ``name``
    (``repro.<name>`` in a profiler trace)."""

    __slots__ = ("_name", "_store", "_note", "_frame")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        store = self._store = _store
        nid, label = store._ids.get(self._name) or store._name(self._name)
        note = self._note = jax.profiler.TraceAnnotation(label)
        note.__enter__()
        self._frame = store.open(nid)
        return self

    def __exit__(self, *exc):
        self._store.close(self._frame)
        self._note.__exit__(*exc)
        return False


def count(name: str, n=1) -> None:
    """Add ``n`` to the counter ``name`` of the innermost open span."""
    stack = _store._stack.open
    if stack:
        frame = stack[-1]
        c = frame[1]
        if c is None:
            frame[1] = {name: n}
        else:
            c[name] = c.get(name, 0) + n


def spans(name: str, last: int = 1) -> list:
    """The last ``last`` spans called ``name``, each with its subtree (see
    :meth:`Store.spans`)."""
    return _store.spans(name, last)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        count("compiles")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
