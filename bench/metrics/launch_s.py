"""launch_s: map_reduce call to last result, averaged over every launch
of the window (host clock)."""


def read(obs):
    launches = obs.get("launches")
    if not launches:
        return None
    return sum(r["t_s"] for r in launches) / len(launches)
