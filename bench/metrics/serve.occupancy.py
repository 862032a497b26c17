"""serve.occupancy: share of decode slots that produced a token, over
the window's decode steps (decoded / (steps x slots))."""


def read(obs):
    w = obs.get("window")
    if not w or not w["steps"]:
        return None
    return 100.0 * w["decoded"] / (w["steps"] * obs["slots"])
