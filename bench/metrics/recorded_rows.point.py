"""Events the trace records per point (the program's ``rows`` counter,
one per event before consecutive identical events collapse), over the
points of the window."""

import program_spans


def read(obs):
    got = program_spans.counter(obs, "rows")
    return None if got is None else got[0] / got[1]
