"""launch.waves: waves the launch policy cut each launch into, averaged
over the window's launches (MapReduceReport.waves)."""


def read(obs):
    launches = obs.get("launches")
    if not launches:
        return None
    return sum(r["waves"] for r in launches) / len(launches)
