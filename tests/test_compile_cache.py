"""CompileCache disk-tier policy: LRU-by-bytes eviction, jax-version
stamping, and the env-var budget knob."""
import os
import time

import numpy as np
import pytest

from repro.core.compile_cache import CompileCache, _version_tag


def _fn(salt):
    """A distinct tiny program per salt (closure const changes the key)."""
    def f(x, _s=salt):
        return x * _s + _s
    return f


X = (np.ones((4,), np.float32),)


def _aotx_files(d):
    return sorted(f for f in os.listdir(d) if f.endswith(".aotx"))


def _entry_size(tmp_path):
    d = str(tmp_path / "probe")
    c = CompileCache(cache_dir=d)
    c.compile(_fn(0), X, extras=("probe",))
    files = _aotx_files(d)
    assert files, "spill did not happen; cannot size an entry"
    return os.path.getsize(os.path.join(d, files[0]))


def test_lru_eviction_by_bytes(tmp_path):
    size = _entry_size(tmp_path)
    d = str(tmp_path / "aot")
    cache = CompileCache(cache_dir=d, max_bytes=int(size * 1.5))
    cache.compile(_fn(1), X, extras=("a",))
    time.sleep(0.05)                       # distinct mtimes for LRU order
    cache.compile(_fn(2), X, extras=("b",))
    # two entries > budget: the older one must have been evicted
    assert cache.stats["evictions"] >= 1
    assert len(_aotx_files(d)) == 1
    fresh = CompileCache(cache_dir=d)      # new process, same dir
    _, src_a = fresh.compile(_fn(1), X, extras=("a",))
    _, src_b = fresh.compile(_fn(2), X, extras=("b",))
    assert src_a == "compiled"             # evicted: cold again
    assert src_b == "disk"                 # survivor: warm across processes


def test_lru_recency_refreshed_by_disk_hit(tmp_path):
    size = _entry_size(tmp_path)
    d = str(tmp_path / "aot")
    warm = CompileCache(cache_dir=d)       # unbounded writer
    warm.compile(_fn(1), X, extras=("a",))
    time.sleep(0.05)
    warm.compile(_fn(2), X, extras=("b",))
    time.sleep(0.05)
    # a disk hit on A refreshes its mtime past B's
    reader = CompileCache(cache_dir=d, max_bytes=int(size * 2.5))
    _, src = reader.compile(_fn(1), X, extras=("a",))
    assert src == "disk"
    time.sleep(0.05)
    reader.compile(_fn(3), X, extras=("c",))   # spill -> prune over budget
    assert reader.stats["evictions"] >= 1
    check = CompileCache(cache_dir=d)
    _, src_a = check.compile(_fn(1), X, extras=("a",))
    _, src_b = check.compile(_fn(2), X, extras=("b",))
    assert src_a == "disk"                 # recently used: kept
    assert src_b == "compiled"             # least recently used: evicted


def test_alien_version_spills_are_dropped(tmp_path):
    d = str(tmp_path / "aot")
    os.makedirs(d)
    stale = os.path.join(d, "0" * 64 + ".deadbeef.aotx")
    with open(stale, "wb") as f:
        f.write(b"serialized-by-another-jax")
    cache = CompileCache(cache_dir=d)
    cache.compile(_fn(1), X, extras=("a",))
    assert not os.path.exists(stale)
    assert cache.stats["version_drops"] == 1
    # current-version spills carry the version tag in their name
    assert all(f.endswith(f".{_version_tag()}.aotx")
               for f in _aotx_files(d))


def test_max_bytes_env_knob(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_COMPILE_CACHE_MAX_BYTES", "12345")
    cache = CompileCache(cache_dir=str(tmp_path / "aot"))
    assert cache.max_bytes == 12345
    monkeypatch.delenv("REPRO_COMPILE_CACHE_MAX_BYTES")
    assert CompileCache(cache_dir=str(tmp_path / "aot2")).max_bytes is None
    # explicit argument wins over the env default
    monkeypatch.setenv("REPRO_COMPILE_CACHE_MAX_BYTES", "12345")
    assert CompileCache(cache_dir=str(tmp_path / "aot3"),
                        max_bytes=77).max_bytes == 77


def test_unbounded_cache_never_evicts(tmp_path):
    d = str(tmp_path / "aot")
    cache = CompileCache(cache_dir=d)
    for s in range(3):
        cache.compile(_fn(s + 10), X, extras=("u", s))
    assert cache.stats["evictions"] == 0
    assert len(_aotx_files(d)) == 3


@pytest.mark.parametrize("persistent", [True, False])
def test_memory_tier_unaffected_by_budget(tmp_path, persistent):
    """Eviction is a DISK policy: the in-memory tier still hits."""
    cache = CompileCache(cache_dir=str(tmp_path / "aot"),
                         persistent=persistent, max_bytes=1)
    cache.compile(_fn(1), X, extras=("m",))
    _, src = cache.compile(_fn(1), X, extras=("m",))
    assert src == "memory"


def test_default_dir_is_jax_cache_dir_or_checkout(monkeypatch):
    from repro.core import compile_cache as cc
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/jcc")
    assert cc.default_cache_dir() == "/somewhere/jcc"
    assert CompileCache().cache_dir == "/somewhere/jcc"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.default_cache_dir() == os.path.join(root, ".aot_cache")


def test_source_salt_keys_on_content_not_mtime(tmp_path, monkeypatch):
    """A copied checkout (new mtimes, same sources) keeps its keys; an
    edited source changes them."""
    import shutil
    from repro.core import compile_cache as cc
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(cc.__file__)))
    copy = tmp_path / "repro"
    shutil.copytree(pkg, copy, ignore=shutil.ignore_patterns("__pycache__"))

    def salt(root):
        monkeypatch.setattr(cc, "_TREE_SALT", None)
        monkeypatch.setattr(cc, "__file__",
                            str(root / "core" / "compile_cache.py"))
        return cc._source_tree_salt()

    original = salt(copy)
    for p in copy.rglob("*.py"):
        os.utime(p, (1, 1))
    assert salt(copy) == original
    with open(copy / "core" / "backend.py", "a") as f:
        f.write("\n# edit\n")
    assert salt(copy) != original


def test_load_and_spill_errors_are_counted(tmp_path):
    d = str(tmp_path / "aot")
    cache = CompileCache(cache_dir=d)
    cache.compile(_fn(1), X, extras=("e",))
    (name,) = _aotx_files(d)
    with open(os.path.join(d, name), "wb") as f:
        f.write(b"not a pickle")
    fresh = CompileCache(cache_dir=d)
    _, src = fresh.compile(_fn(1), X, extras=("e",))
    assert src == "compiled"              # a broken spill is recompiled
    assert fresh.stats["load_errors"] == 1
    assert fresh.last_error.startswith("load ")
    blocked = CompileCache(cache_dir=str(tmp_path / "file"))
    (tmp_path / "file").write_text("a file where the dir should be")
    blocked.compile(_fn(2), X, extras=("e",))
    assert blocked.stats["spill_errors"] == 1
    assert blocked.last_error.startswith("spill")
