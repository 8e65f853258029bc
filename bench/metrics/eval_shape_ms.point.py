"""Host time per point of ``jax.eval_shape`` in ``profile_traced`` (the
program's ``eval_shape`` span: the app's trace, with the recorder's appends
and struct interning inside it), in ms, over the points of the window."""

import program_spans


def read(obs):
    return program_spans.total_ms(obs, "eval_shape")
