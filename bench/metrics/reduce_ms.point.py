"""Host time per point of the reduction (``CommPatternProfiler.
from_recorder`` on the device backend, inside the ``trace_observer``
hook), in ms, over the points of the window."""


def read(obs):
    span, n = obs.get("span_s"), obs.get("points")
    if not span or not n or "reduce" not in span:
        return None
    return 1e3 * span["reduce"] / n
