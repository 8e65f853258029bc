"""Host time per point of the Frame build (``Frame.from_profiles`` once per
sweep), in ms, over the points of the window."""


def read(obs):
    span, n = obs.get("span_s"), obs.get("points")
    if not span or not n or "frame" not in span:
        return None
    return 1e3 * span["frame"] / n
