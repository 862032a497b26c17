"""The manifest's names and files, the traffic generators' use of the
seed, and that a cell, a traffic mix and a metric are added by files and
entries alone."""
import json
import os
import shutil

import numpy as np
import pytest

import small_cells
from harness import manifest, traffic as gen

ROOT = small_cells.ROOT


@pytest.fixture(scope="module")
def bench_manifest():
    return manifest.load_manifest(ROOT)


def test_names_units_and_files(bench_manifest):
    assert manifest.problems(bench_manifest, ROOT) == []
    m = bench_manifest
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in m[kind]]
        assert len(names) == len(set(names))
        assert all(manifest.NAME_RE.match(n) for n in names)
    for mt in m["end_to_end"] + m["per_layer"]:
        assert manifest.UNIT_RE.match(mt["unit"])
    assert {e["name"] for e in m["end_to_end"]} >= {"setup_s"}


def test_every_cell_resolves_with_its_files(bench_manifest):
    for w in bench_manifest["workloads"]:
        c = manifest.resolve(w["name"], bench_manifest, ROOT)
        assert os.path.isfile(manifest.system_path(ROOT, c.system))
        assert hasattr(c.reference(), "__file__")
        e2e = {m["name"] for m in c.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert c.per_layer
        for m in c.per_layer:
            assert m["moves"] in e2e
            assert os.path.isfile(manifest.reader_path(ROOT, m["name"]))


def test_unknown_names_are_refused(bench_manifest):
    with pytest.raises(manifest.ManifestError):
        manifest.resolve("no-such-cell", bench_manifest, ROOT)


def test_open_loop_schedule_follows_the_seed():
    tr = manifest.load_json(manifest.traffic_path(ROOT, "chat"))
    a = gen.schedule(tr, 30.0, 2**33 + 1, 151936)
    b = gen.schedule(tr, 30.0, 2**33 + 1, 151936)
    c = gen.schedule(tr, 30.0, 2**33 + 2, 151936)
    key = lambda s: [(x.due_s, x.max_new, x.prompt.tobytes()) for x in s]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # every seed gets the same work at the same times; the seed draws the
    # prompts' tokens
    assert [(x.due_s, len(x.prompt), x.max_new) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new) for x in c]
    assert any(x.prompt.tobytes() != y.prompt.tobytes()
               for x, y in zip(a, c))
    assert all(0 <= x.due_s <= 30.0 for x in a)
    assert all(tr["prompt"]["min"] <= len(x.prompt) <= tr["prompt"]["max"]
               for x in a)
    assert all(tr["output"]["min"] <= x.max_new <= tr["output"]["max"]
               for x in a)


def test_launch_inputs_follow_the_seed():
    c = small_cells.cell("launch-16k-repeat")
    drv = manifest.load_module(manifest.system_path(ROOT, "launch"))
    a = drv.make_inputs(2**33 + 1, 8, (4, 16), "bfloat16")
    b = drv.make_inputs(2**33 + 1, 8, (4, 16), "bfloat16")
    d = drv.make_inputs(2**33 + 2, 8, (4, 16), "bfloat16")
    assert np.array_equal(a, b) and not np.array_equal(a, d)
    assert c.traffic["shift_per_launch"] > 0


def test_a_cell_traffic_and_metric_are_added_by_files_alone(tmp_path):
    """A throwaway cell with its own traffic and per-layer metric, added
    as new files and manifest entries in a copy of bench/: the harness
    resolves them and reads the metric, with no file edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = manifest.load_manifest(ROOT)
    (root / "bench" / "traffic" / "burst.json").write_text(json.dumps(
        {"loop": "closed", "shift_per_launch": 3,
         "max_launches": 2, "warmup_launches_max": 1}))
    (root / "bench" / "metrics" / "launch.throwaway_n.py").write_text(
        "def read(obs):\n    return len(obs['launches'])\n")
    m["workloads"].append({"name": "launch-16k-burst", "config": "launch-16k",
                           "traffic": "burst", "chips": 1,
                           "why": "throwaway"})
    m["per_layer"].append({"name": "launch.throwaway_n", "unit": "launches",
                           "better": "higher", "source": "program_counter",
                           "layer": "launch policy", "moves": "launch_s",
                           "workloads": ["launch-16k-burst"]})
    for e in m["end_to_end"]:
        if "workloads" in e and "launch-16k-repeat" in e["workloads"]:
            e["workloads"].append("launch-16k-burst")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    mf = manifest.load_manifest(str(root))
    assert manifest.problems(mf, str(root)) == []
    c = manifest.resolve("launch-16k-burst", mf, str(root))
    assert c.traffic["shift_per_launch"] == 3
    assert [x["name"] for x in c.per_layer] == ["launch.throwaway_n"]
    reader = manifest.load_module(manifest.reader_path(str(root),
                                                       "launch.throwaway_n"))
    assert reader.read({"launches": [{}, {}]}) == 2
