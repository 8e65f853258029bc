"""Host time per point spent interning new communication structures (the
program's ``intern_ns`` counter, summed on the struct table's miss path),
in ms, over the points of the window."""

import program_spans


def read(obs):
    got = program_spans.counter(obs, "intern_ns")
    return None if got is None else got[0] / 1e6 / got[1]
