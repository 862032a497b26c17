"""The benchmark's cells shrunk to CPU test size, driven through the
harness with its look for a chip skipped."""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from harness import manifest, runner  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

SMOKE_QWEN = dict(hidden_size=128, intermediate_size=256,
                  num_attention_heads=10, num_key_value_heads=2,
                  head_dim=16, num_hidden_layers=2, vocab_size=512,
                  rope_theta=10000.0)


def cell(name: str, root: str = ROOT):
    c = manifest.resolve(name, manifest.load_manifest(root), root)
    if c.system == "launch":
        c.config = dict(c.config, instances=384, check_sample=64)
    elif c.system == "serve":
        c.config = dict(c.config, **SMOKE_QWEN,
                        program={"arch": "qwen3-14b", "smoke": True},
                        engine=dict(slots=4, page_size=8, pages_per_slot=16,
                                    pool_pages=64, kernel="gather"))
        c.traffic = dict(c.traffic, rate_per_s=4.0,
                         prompt=dict(median=20, sigma=0.5, min=8, max=60),
                         output=dict(median=8, sigma=0.5, min=3, max=20))
    return c


def run(c, seed: int = 12345, seconds: float = 2.0, trace: bool = False,
        control: bool = False):
    import jax
    return runner.run_cell(c, jax.devices()[:1], PEAKS, seed=seed,
                           seconds=seconds, trace=trace,
                           t_start=time.perf_counter(), control=control)
