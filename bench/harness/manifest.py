"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name the manifest gives it:

    bench/configs/<config file>      sizes (JSON), named by the config entry
    bench/configs/<config>.py        its plain reference, beside the sizes
    bench/traffic/<traffic>.json     parameters of the mix
    bench/systems/<system>.py        the general code that builds and
                                     drives the system kind the config
                                     file names ("system")
    bench/metrics/<metric>.py        one reader per metric: read(obs)

Adding a cell, a mix or a metric therefore adds files and manifest
entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


class ManifestError(ValueError):
    """The manifest or a file it names is missing or malformed."""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict                      # the configuration file, as run
    config_dir: str                   # directory of the configuration file
    traffic: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    root: str = ROOT

    @property
    def system(self) -> str:
        return self.config["system"]

    def reference(self):
        """The configuration's plain reference module (``<config>.py``
        beside its sizes)."""
        return load_module(os.path.join(self.config_dir,
                                        self.config_name + ".py"))


def load_manifest(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


def load_module(path: str):
    """Import a file by path (names may hold '-' and '.')."""
    if not os.path.isfile(path):
        raise ManifestError(f"missing file {path}")
    name = "bench_" + re.sub(r"[^A-Za-z0-9_]", "_",
                             os.path.relpath(path, BENCH))[:-3]
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_dir(root: str) -> str:
    return os.path.join(root, "bench")


def traffic_path(root: str, name: str) -> str:
    return os.path.join(bench_dir(root), "traffic", name + ".json")


def reader_path(root: str, name: str) -> str:
    return os.path.join(bench_dir(root), "metrics", name + ".py")


def system_path(root: str, system: str) -> str:
    return os.path.join(bench_dir(root), "systems", system + ".py")


def _applies(metric: dict, cell: str, reported: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if reported is None:              # an end-to-end metric for every cell
        return True
    return metric.get("moves") in reported


def resolve(workload: str, manifest: dict, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in manifest.get("workloads", [])}
    if workload not in cells:
        raise ManifestError(f"no workload {workload!r} in BENCHMARK.json "
                            f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest.get("configs", [])}
    if w["config"] not in configs:
        raise ManifestError(f"workload {workload!r} names unknown config "
                            f"{w['config']!r}")
    centry = configs[w["config"]]
    cpath = os.path.join(root, centry["file"])
    config = load_json(cpath)
    if "system" not in config:
        raise ManifestError(f"{cpath} names no 'system'")
    traffic = load_json(traffic_path(root, w["traffic"]))
    e2e = [m for m in manifest.get("end_to_end", [])
           if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in manifest.get("per_layer", [])
                 if _applies(m, workload, reported)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], traffic_name=w["traffic"],
                config=config, config_dir=os.path.dirname(cpath),
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=root)


def problems(manifest: dict, root: str = ROOT) -> List[str]:
    """Every way the manifest breaks the naming rules or names a file that
    is not there (empty when sound)."""
    out: List[str] = []

    def name_ok(kind: str, s) -> None:
        if not isinstance(s, str) or not NAME_RE.match(s):
            out.append(f"{kind} name {s!r}")

    for c in manifest.get("configs", []):
        name_ok("config", c.get("name"))
        for k in c.get("reduced", []):
            name_ok("reduced key", k)
        if not PATH_RE.match(c.get("file", "")) or ".." in c["file"]:
            out.append(f"config file {c.get('file')!r}")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            out.append(f"config file {c['file']} missing")
        else:
            ref = os.path.join(os.path.dirname(os.path.join(root, c["file"])),
                               c["name"] + ".py")
            if not os.path.isfile(ref):
                out.append(f"reference {ref} missing")
    for w in manifest.get("workloads", []):
        name_ok("workload", w.get("name"))
        name_ok("config", w.get("config"))
        name_ok("traffic", w.get("traffic"))
        if not os.path.isfile(traffic_path(root, w.get("traffic", ""))):
            out.append(f"traffic {w.get('traffic')} missing")
    for kind in ("end_to_end", "per_layer"):
        for m in manifest.get(kind, []):
            name_ok("metric", m.get("name"))
            if not UNIT_RE.match(str(m.get("unit", ""))):
                out.append(f"unit {m.get('unit')!r} of {m.get('name')}")
            if m.get("better") not in ("lower", "higher"):
                out.append(f"better of {m.get('name')}")
            if not os.path.isfile(reader_path(root, m.get("name", ""))):
                out.append(f"reader of {m.get('name')} missing")
    for w in manifest.get("workloads", []):
        try:
            cell = resolve(w["name"], manifest, root)
        except ManifestError as e:
            out.append(str(e))
            continue
        if not os.path.isfile(system_path(root, cell.system)):
            out.append(f"code for system {cell.system!r} missing")
    return out
