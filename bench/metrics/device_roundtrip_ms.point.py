"""Time per point of the reduction backend's calls to the device (the
program's ``device_roundtrip`` spans: from an op's NumPy inputs to its
NumPy output, so the puts, the dispatch, the device's work, the wait for
it and the read back), in ms, over the points of the window."""

import program_spans


def read(obs):
    return program_spans.total_ms(obs, "device_roundtrip")
