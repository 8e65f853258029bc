"""Host time per point of the struct table's lazy slab materialization
(the program's ``materialize`` span), in ms, over the points of the
window."""

import program_spans


def read(obs):
    return program_spans.total_ms(obs, "materialize")
