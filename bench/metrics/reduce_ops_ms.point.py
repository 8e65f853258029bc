"""Time per point inside the reduction backend's ops (the program's
``matmul``, ``block_reduce``, ``segment_reduce``, ``factorize``,
``pair_counts`` and ``pair_codes`` spans, transfers included), in ms,
over the points of the window."""

import program_spans

OPS = (
    "matmul",
    "block_reduce",
    "segment_reduce",
    "factorize",
    "pair_counts",
    "pair_codes",
)


def read(obs):
    return program_spans.total_ms(obs, *OPS)
