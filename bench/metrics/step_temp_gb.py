"""Temporary device memory of the step program, from the compiler's memory
analysis (``compiled.memory_analysis().temp_size_in_bytes``), in GB."""


def read(obs):
    temp = obs.get("step_temp_bytes")
    return None if temp is None else temp / 1e9
