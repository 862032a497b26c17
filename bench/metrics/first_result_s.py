"""first_result_s: map_reduce call to the first harvested wave, averaged
over every launch of the window (host clock) — the paper's interactivity
measure."""


def read(obs):
    launches = obs.get("launches")
    if not launches:
        return None
    return sum(r["first_s"] for r in launches) / len(launches)
