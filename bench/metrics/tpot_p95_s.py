"""tpot_p95_s: 95th percentile (nearest rank) over every request due in
the window of its time per output token after the first, (done - first)
/ (tokens - 1) (host clock). A failed request counts as a miss (the
drain allowance per token)."""
from harness.stats import nearest_rank


def read(obs):
    reqs = obs.get("requests")
    if not reqs:
        return None
    miss = obs["seconds"] + obs["drain_s"]
    return nearest_rank([r["tpot_s"] if r["ok"] and r["tpot_s"] is not None
                         else miss for r in reqs], 0.95)
