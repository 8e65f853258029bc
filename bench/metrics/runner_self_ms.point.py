"""Self time per point of the runner's ``point`` span (the span less its
child spans ``eval_shape`` and ``reduce``: the app's config, mesh and
topology, the roofline stamp, and the runner's and the profiler's glue
around the trace and the reduction), in ms, over the points of the
window."""

import program_spans


def read(obs):
    return program_spans.self_ms(obs, "point")
