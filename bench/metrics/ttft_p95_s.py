"""ttft_p95_s: 95th percentile (nearest rank) over every request due in
the window of the time from its due time to its first token (host
clock). A request that failed or never finished counts as a miss: it
reads as the window's end plus the drain allowance."""
from harness.stats import nearest_rank


def read(obs):
    reqs = obs.get("requests")
    if not reqs:
        return None
    miss = obs["seconds"] + obs["drain_s"]
    return nearest_rank([r["ttft_s"] if r["ok"] else miss for r in reqs],
                        0.95)
