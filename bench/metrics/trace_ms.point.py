"""Host time per point spent outside the reduction, in ms: the
``run_experiment`` call (trace with ``eval_shape`` into the trace buffer,
the runner's own work) minus the reduce span inside it, over the points of
the window."""


def read(obs):
    span, n = obs.get("span_s"), obs.get("points")
    if not span or not n or "trace" not in span:
        return None
    return 1e3 * (span["trace"] - span.get("reduce", 0.0)) / n
