"""XLA compiles and persistent-cache loads inside the window's points (the
program's ``compiles`` counter, one for each, summed): every shape is
warmed up before the window, so this should read 0."""

import program_spans


def read(obs):
    got = program_spans.counter(obs, "compiles")
    return None if got is None else got[0]
