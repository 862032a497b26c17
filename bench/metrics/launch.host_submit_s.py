"""launch.host_submit_s: host seconds per launch spent submitting waves
(sum over the launch's wave records of t_schedule + t_stage +
t_dispatch; compile lookups and staging fall inside)."""


def read(obs):
    launches = obs.get("launches")
    if not launches:
        return None
    return sum(r["host_submit_s"] for r in launches) / len(launches)
