"""Share of the HBM roofline that one PCG–AMG solve reaches.

The least time is the solve's compulsory bytes per chip (each stencil,
restriction, prolongation and Krylov vector pass at every level reading its
operands once and writing its result once, counted from the configuration's
shapes by its ``compulsory_bytes``) over the chip's published HBM
bandwidth; it is divided by the device time of a step, the chips' mean busy
time in the traced window over the steps in it.
"""


def read(obs):
    trace, steps = obs.get("trace"), obs.get("steps")
    if trace is None or not steps or "compulsory_bytes_per_chip" not in obs:
        return None
    least_s = obs["compulsory_bytes_per_chip"] / obs["peaks"].hbm_bytes_per_s
    return 100.0 * least_s / (trace.mean_busy_s / steps)
