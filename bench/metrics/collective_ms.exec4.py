"""Device time of collective operations per step, in ms: the union of the
collective ops' intervals in the traced window, averaged over the chips,
over the steps in the window."""


def read(obs):
    trace, steps = obs.get("trace"), obs.get("steps")
    if trace is None or not steps:
        return None
    coll = sum(trace.collective_s) / trace.n_chips
    return 1e3 * coll / steps if coll > 0 else None
