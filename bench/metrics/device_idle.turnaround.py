"""Idle share of the device in the traced window, in %: one minus the
chips' mean busy time (the union of op intervals) over the window."""


def read(obs):
    trace = obs.get("trace")
    return None if trace is None else trace.idle_pct
