"""WaveController ``devices>1`` lane autoscaling on a REAL multi-device
mesh. CPU CI has one device, so this module runs the scenario in a
subprocess with ``--xla_force_host_platform_device_count=8`` (the flag
must be set before jax initializes — it cannot be applied in-process
once conftest has imported jax)."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import tempfile

import jax
import numpy as np

assert jax.device_count() == 8, f"expected 8 fake devices, got {jax.device_count()}"

from repro.core.autoscale import WaveController
from repro.core.backend import PipelinedBackend
from repro.core.compile_cache import CompileCache
from repro.core.llmr import LLMapReduce


def app(x):
    return (x * 2.0).sum(axis=-1)


# controller policy on the real device count: hierarchy, exact reshape
c = WaveController(n_tasks=4096, devices=len(jax.devices()), start_wave=512)
d = c.next_wave(4096)
assert d.inner_lanes > 1, d
assert d.wave % d.inner_lanes == 0, d
assert d.wave // d.inner_lanes >= 8, d

# end to end: auto-sized waves over a real 8-way mesh must produce
# hierarchical (core > 1) fan-outs AND the right numbers
mesh = jax.make_mesh((8,), ("data",))
be = PipelinedBackend(mesh=mesh,
                      cache=CompileCache(cache_dir=tempfile.mkdtemp()))
inputs = np.random.default_rng(0).standard_normal((512, 8)).astype(np.float32)
llmr = LLMapReduce(mesh=mesh, wave_size="auto", backend=be)
out, rep = llmr.map_reduce(app, inputs)
np.testing.assert_allclose(np.asarray(out), inputs.sum(-1) * 2.0,
                           rtol=1e-4, atol=1e-4)
assert rep.n_instances == 512
hier = [r for r in rep.records if r.fanout.get("core", 1) > 1]
assert hier, [r.fanout for r in rep.records]
lanes = [d.inner_lanes for d in rep.autoscale]
assert max(lanes) > 1, lanes
print(f"MULTIDEVICE_OK waves={rep.waves} "
      f"max_core={max(r.fanout.get('core', 1) for r in rep.records)}")
"""


def test_lane_autoscaling_on_eight_fake_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        "--xla_cpu_multi_thread_eigen=false")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(ROOT, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr}\nstdout:\n{proc.stdout}"
    assert "MULTIDEVICE_OK" in proc.stdout, proc.stdout


PLACEMENT = r"""
import tempfile

import jax
import numpy as np

from repro.core.backend import PipelinedBackend
from repro.core.compile_cache import CompileCache
from repro.dist.backend import DistributedBackend


def app(x):
    return (x * 2.0).sum(axis=-1)


ids = lambda a: sorted(d.id for d in a.sharding.device_set)
x = np.random.default_rng(1).standard_normal((13, 8)).astype(np.float32)

# a wave the 8-way task axis does not divide is padded onto the mesh,
# not compiled unsharded onto device 0; the pad lanes never come back
be = PipelinedBackend(mesh=jax.make_mesh((8,), ("data",)),
                      cache=CompileCache(cache_dir=tempfile.mkdtemp()))
out, rec = be.dispatch(app, x, 13).result()
assert out.shape == (13,) and ids(out) == list(range(8)), (out.shape, ids(out))
np.testing.assert_allclose(np.asarray(out), x.sum(-1) * 2.0, rtol=1e-5)

# a one-device executable reloaded from disk in an 8-device process runs
# on its own device (a reload binds to every local device unless told)
d = tempfile.mkdtemp()
CompileCache(cache_dir=d).compile(app, (x,))
compiled, src = CompileCache(cache_dir=d).compile(app, (x,))
assert src == "disk", src
np.testing.assert_allclose(np.asarray(compiled(x)), x.sum(-1) * 2.0, rtol=1e-5)

# thread nodes each own their devices: waves run there, and each node
# reports where at registration
# (one device each: a lone device used to get no mesh, so every node ran
# on device 0)
dist = DistributedBackend(n_nodes=8, heartbeat_timeout_s=30.0,
                          cache=CompileCache(cache_dir=tempfile.mkdtemp()))
try:
    xs = np.tile(x, (8, 1))
    out, _ = dist.launch(app, xs, len(xs))
    np.testing.assert_allclose(np.asarray(out), xs.sum(-1) * 2, rtol=1e-5)
    placed = {nid: ids(a.backend.dispatch(app, x[:8], 8).result()[0])
              for nid, a in dist.agents.items()}
    roll = dist.registry.rollup()
    reported = {nid: roll[nid]["device"]["devices"] for nid in placed}
    assert placed == reported, (placed, reported)
    assert sorted(v[0] for v in placed.values()) == list(range(8)), placed
    assert all(roll[n]["device"]["platform"] == "cpu" for n in placed)
finally:
    dist.close()
print("PLACEMENT_OK")
"""


def test_waves_and_nodes_run_on_the_devices_they_own():
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        "--xla_cpu_multi_thread_eigen=false")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(ROOT, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", PLACEMENT], env=env,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr}\nstdout:\n{proc.stdout}"
    assert "PLACEMENT_OK" in proc.stdout, proc.stdout
