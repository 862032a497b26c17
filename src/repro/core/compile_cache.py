"""Persistent AOT compile cache: the launch-side analogue of the paper's
pre-staged Wine environment.

The paper pays environment setup ONCE (the Wine prefix is built ahead of
time and staged to node-local disk), so instance N's start cost is pure
process spawn. The JAX analogue of "environment setup" is trace+lower+
compile; this module makes that cost a one-time cost *across processes*:

  * executables are keyed by a CONTENT fingerprint — a hash of the mapped
    function's source (plus bounded closure/default/global context,
    including sampled VALUES of captured arrays), the abstract input
    pytree (structure + shapes + dtypes), the mesh shape, the jit
    options, and a salt over the ``repro`` package's own sources (so
    edits anywhere in the call graph inside the package invalidate the
    disk tier) — never by ``id(fn)``, which CPython reuses after garbage
    collection and can silently alias two different programs;
  * compiled executables are spilled to disk via
    ``jax.experimental.serialize_executable`` and re-loaded by later
    processes, skipping trace+compile entirely (a warm launch pays only
    deserialization, the same way a warm Wine prefix pays only exec()).

Both the launcher backends (``core.backend``) and the serving engine
(``serve.engine``) compile through one shared cache, so a model prefilled
by serve is already warm for launch and vice versa.
"""
from __future__ import annotations

import hashlib
import inspect
import os
import pickle
import re
import tempfile
import threading
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

_MAX_BYTES_ENV = "REPRO_COMPILE_CACHE_MAX_BYTES"
# the fixed in-checkout home of the disk tier (listed in .gitignore)
_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".aot_cache")


def default_cache_dir() -> str:
    """Where a ``CompileCache`` spills unless told otherwise: the
    directory ``$JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX
    keeps its own persistent cache there too), else ``.aot_cache`` at the
    root of the checkout. A fixed path: the directory is part of what a
    later process must find again."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_DIR

_VERSION_TAG: Optional[str] = None


def _version_tag() -> str:
    """Short digest of the jax version, embedded in every spill's filename.
    Executables serialized by one jax are not trusted by another: a
    different-version file is dead weight that can never hit (the
    fingerprint already folds in ``jax.__version__``), so pruning deletes
    it on sight instead of letting the dir grow without bound."""
    global _VERSION_TAG
    if _VERSION_TAG is None:
        _VERSION_TAG = hashlib.sha256(
            ("jax:" + jax.__version__).encode()).hexdigest()[:8]
    return _VERSION_TAG


# ----------------------------------------------------------------------
# Content fingerprinting
# ----------------------------------------------------------------------

def _obj_sig(v: Any, depth: int = 2) -> str:
    """A stable signature for a closure cell / referenced global.

    Bounded: arrays collapse to shape/dtype + a sampled value digest,
    callables to a code hash plus (``depth`` levels of) their own closure
    and default signatures — enough to distinguish ``f`` calling ``g1``
    from ``f`` calling ``g2`` even when g1/g2 come from one factory over
    different data. Memory addresses are stripped before hashing, so
    signatures are stable across processes.
    """
    if isinstance(v, (int, float, bool, str, bytes, type(None))):
        return repr(v)
    if inspect.ismodule(v):
        return f"mod:{v.__name__}"
    # array-likes before callables; modules also expose .shape/.dtype
    # attributes (as functions), hence the tuple() guard
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        try:
            return f"arr{tuple(v.shape)}:{v.dtype}:{_array_digest(v)}"
        except TypeError:
            pass
    # containers: recurse over EVERY element so interior arrays get VALUE
    # digests (repr of a dict of weights truncates and would alias
    # different values); the signature string is hashed if it grows long,
    # so the key stays bounded while the content walk is complete
    if isinstance(v, (list, tuple)):
        sig = ";".join(_obj_sig(x, depth) for x in v)
        return f"{type(v).__name__}[{len(v)}]:({_squash(sig)})"
    if isinstance(v, dict):
        try:
            keys = sorted(v, key=repr)
        except TypeError:
            keys = list(v)
        sig = ";".join(f"{k!r}={_obj_sig(v[k], depth)}" for k in keys)
        return f"dict[{len(v)}]:({_squash(sig)})"
    if callable(v):
        code = getattr(v, "__code__", None)
        if code is not None and depth > 0:
            consts = tuple(c for c in code.co_consts
                           if isinstance(c, (int, float, bool, str, bytes,
                                             type(None))))
            ctx = []
            for cell in getattr(v, "__closure__", None) or ():
                try:
                    ctx.append(_obj_sig(cell.cell_contents, depth - 1))
                except ValueError:
                    ctx.append("<empty>")
            for d in getattr(v, "__defaults__", None) or ():
                ctx.append(_obj_sig(d, depth - 1))
            return ("fn:" + hashlib.sha256(code.co_code).hexdigest()[:16]
                    + f":{consts!r}:{';'.join(ctx)}")
        return "call:" + getattr(v, "__qualname__", type(v).__name__)
    r = re.sub(r" at 0x[0-9a-fA-F]+", "", repr(v))
    if len(r) > 256:
        return "obj:" + hashlib.sha256(r.encode()).hexdigest()[:16]
    return r


def _squash(sig: str, limit: int = 512) -> str:
    return (sig if len(sig) <= limit
            else hashlib.sha256(sig.encode()).hexdigest()[:16])


def _array_digest(v: Any) -> str:
    """Digest of an array's VALUES, not just shape/dtype: jit bakes
    closed-over arrays into the program as constants, so two closures over
    same-shaped but different-valued arrays are different programs. Large
    arrays are sampled (head + stride + tail) to bound fingerprint cost."""
    try:
        flat = np.asarray(v).reshape(-1)
        if flat.size > 65536:
            step = max(1, flat.size // 16384)
            flat = np.concatenate([flat[:16384], flat[::step][:16384],
                                   flat[-16384:]])
        return hashlib.sha256(
            np.ascontiguousarray(flat).tobytes()).hexdigest()[:16]
    except Exception:
        return "opaque"


def _source_hash(fn: Callable) -> str:
    """Hash of what the function *is*: source text (or bytecode), closure
    cells, defaults, and one level of referenced globals.

    Deliberately NOT memoized on the function object: closure cells and
    module globals are rebindable and closed-over arrays are mutable in
    place, so a frozen digest could serve a stale executable — the exact
    failure class the content fingerprint exists to eliminate. The cost
    is bounded (sampled array digests, one level of context) and the
    pipelined backend overlaps it with device execution anyway."""
    try:
        src = inspect.getsource(fn)
    except (OSError, TypeError):
        code = getattr(fn, "__code__", None)
        src = code.co_code.hex() if code is not None else repr(
            getattr(fn, "__qualname__", type(fn).__name__))
    parts = [getattr(fn, "__module__", ""), getattr(fn, "__qualname__", ""),
             src]
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            parts.append(_obj_sig(cell.cell_contents))
        except ValueError:          # empty cell
            parts.append("<empty>")
    for d in getattr(fn, "__defaults__", None) or ():
        parts.append("default:" + _obj_sig(d))
    for k, d in (getattr(fn, "__kwdefaults__", None) or {}).items():
        parts.append(f"kwdefault:{k}=" + _obj_sig(d))
    code = getattr(fn, "__code__", None)
    if code is not None:
        g = getattr(fn, "__globals__", {})
        for name in sorted(code.co_names):
            if name in g:
                parts.append(f"{name}={_obj_sig(g[name])}")
    return hashlib.sha256("\n".join(parts).encode()).hexdigest()


_TREE_SALT: Optional[str] = None


def _source_tree_salt() -> str:
    """Digest of the ``repro`` package's source files (path and content),
    computed once per process and folded into every fingerprint.

    The static context walk above sees the launched function, its closure/
    defaults/globals, and one level of referenced callables — it cannot
    see an edit buried deeper in the call graph (fn -> g -> h). Rather
    than serve a stale persisted executable after such an edit, ANY change
    to the package's sources invalidates the disk tier (a conservative
    miss, never a wrong hit). Content, not mtimes: a copied checkout of
    the same sources keeps its keys. Callees in modules outside ``repro``
    remain the caller's responsibility (pass a version via ``extras``)."""
    global _TREE_SALT
    if _TREE_SALT is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        h = hashlib.sha256()
        for dirpath, dirs, files in os.walk(root):
            dirs.sort()
            for f in sorted(files):
                if f.endswith(".py"):
                    p = os.path.join(dirpath, f)
                    with open(p, "rb") as fh:
                        h.update(os.path.relpath(p, root).encode() + b"\0")
                        h.update(fh.read())
        _TREE_SALT = h.hexdigest()[:16]
    return _TREE_SALT


def abstractify(tree: Any) -> Any:
    """Concrete pytree -> ShapeDtypeStruct pytree (identity on structs)."""
    return jax.tree_util.tree_map(
        lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def fingerprint(fn: Callable, abstract_args: tuple,
                mesh: Optional[jax.sharding.Mesh] = None,
                extras: tuple = ()) -> str:
    """Content key for one (program, input signature, topology) triple."""
    leaves, treedef = jax.tree_util.tree_flatten(abstractify(abstract_args))
    avals = "|".join(f"{tuple(l.shape)}:{l.dtype}" for l in leaves)
    # the devices, not just the shape: two one-chip meshes on different
    # chips compile to different executables
    mesh_sig = ((tuple(mesh.shape.items()),
                 tuple(int(d.id) for d in mesh.devices.flat))
                if mesh is not None else ())
    blob = "\n".join([
        _source_hash(fn), str(treedef), avals, str(mesh_sig),
        str(tuple(extras)), jax.__version__, jax.default_backend(),
        _source_tree_salt(),
    ])
    return hashlib.sha256(blob.encode()).hexdigest()


def _device_ids(compiled) -> list:
    """Ids of the devices an executable runs on, in its assignment order:
    a reload must bind it to the same ones (left alone, it binds to every
    local device)."""
    for sh in jax.tree_util.tree_leaves(compiled.input_shardings):
        mesh = getattr(sh, "mesh", None)
        if mesh is not None:
            return [int(d.id) for d in mesh.devices.flat]
        return sorted(int(d.id) for d in sh.device_set)
    return [int(jax.devices()[0].id)]


# ----------------------------------------------------------------------
# The cache
# ----------------------------------------------------------------------

class CompileCache:
    """Two-tier (memory, disk) cache of AOT-compiled executables.

    Disk persistence is best-effort: any serialization failure degrades to
    memory-only caching, never to an error on the launch path — but it is
    counted (``stats["spill_errors"]`` / ``stats["load_errors"]``) and the
    latest one kept in ``last_error``, so a cache that never persists is
    visible to whoever prints its stats.

    The disk tier is bounded: ``max_bytes`` (default from
    ``REPRO_COMPILE_CACHE_MAX_BYTES``; None = unbounded) caps the dir with
    LRU-by-bytes eviction — a disk hit refreshes the entry's recency, a
    spill prunes the least-recently-used entries over budget — and spills
    stamped with a different jax version are deleted on sight (their keys
    can never hit; see ``_version_tag``). Both are reported in ``stats``
    (``evictions`` / ``version_drops``).
    """

    def __init__(self, cache_dir: Optional[str] = None,
                 persistent: bool = True,
                 max_bytes: Optional[int] = None):
        self.cache_dir = cache_dir if cache_dir is not None \
            else default_cache_dir()
        self.persistent = persistent
        if max_bytes is None:
            env = os.environ.get(_MAX_BYTES_ENV)
            max_bytes = int(env) if env else None
        self.max_bytes = max_bytes
        self._mem: dict = {}
        self._lock = threading.Lock()
        self._version_pruned = False
        self.stats = {"mem_hits": 0, "disk_hits": 0, "misses": 0,
                      "spills": 0, "spill_errors": 0, "load_errors": 0,
                      "evictions": 0, "version_drops": 0,
                      "compile_s": 0.0}     # trace+lower+compile wall time
        self.last_error: Optional[str] = None

    # -- tiers ------------------------------------------------------------
    def _path(self, key: str) -> str:
        return os.path.join(self.cache_dir,
                            f"{key}.{_version_tag()}.aotx")

    def _prune_stale_versions(self) -> None:
        """Drop spills stamped with a different jax version (once per
        process per cache): they can never hit — the content fingerprint
        folds the version in — so they are pure dir growth."""
        if self._version_pruned:
            return
        self._version_pruned = True
        suffix = f".{_version_tag()}.aotx"
        try:
            for name in os.listdir(self.cache_dir):
                if name.endswith(".aotx") and not name.endswith(suffix):
                    os.remove(os.path.join(self.cache_dir, name))
                    self.stats["version_drops"] += 1
        except OSError:
            pass

    def _prune_lru(self) -> None:
        """LRU-by-bytes: evict least-recently-USED spills (disk hits
        refresh a file's mtime) until the dir fits ``max_bytes``."""
        if self.max_bytes is None:
            return
        try:
            entries = []
            for name in os.listdir(self.cache_dir):
                if not name.endswith(".aotx"):
                    continue
                p = os.path.join(self.cache_dir, name)
                st = os.stat(p)
                entries.append((st.st_mtime_ns, st.st_size, p))
            total = sum(sz for _, sz, _ in entries)
            for _, sz, p in sorted(entries):
                if total <= self.max_bytes:
                    break
                os.remove(p)
                total -= sz
                self.stats["evictions"] += 1
        except OSError:
            pass

    def _disk_get(self, key: str):
        if not self.persistent:
            return None
        self._prune_stale_versions()
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob, in_tree, out_tree, ids = pickle.load(f)
            from jax.experimental.serialize_executable import (
                deserialize_and_load)
            by_id = {d.id: d for d in jax.devices()}
            compiled = deserialize_and_load(
                blob, in_tree, out_tree,
                execution_devices=[by_id[i] for i in ids])
        except FileNotFoundError:
            return None                      # a plain miss
        except Exception as e:  # noqa: BLE001 — counted; the caller compiles
            self.stats["load_errors"] += 1
            self.last_error = f"load {os.path.basename(path)}: {e!r}"
            return None
        try:
            os.utime(path)                   # refresh LRU recency
        except OSError:
            pass                             # read-only dir: still a hit
        return compiled

    def _disk_put(self, key: str, compiled) -> None:
        if not self.persistent:
            return
        try:
            from jax.experimental.serialize_executable import serialize
            payload = serialize(compiled) + (_device_ids(compiled),)
            os.makedirs(self.cache_dir, exist_ok=True)
            self._prune_stale_versions()
            fd, tmp = tempfile.mkstemp(dir=self.cache_dir, suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                pickle.dump(payload, f)
            os.replace(tmp, self._path(key))     # atomic publish
            self.stats["spills"] += 1
            self._prune_lru()
        except Exception as e:  # noqa: BLE001 — counted; memory tier holds it
            self.stats["spill_errors"] += 1
            self.last_error = f"spill: {e!r}"

    # -- public API -------------------------------------------------------
    def get(self, key: str):
        """-> (compiled, source) where source in {"memory","disk",None}."""
        with self._lock:
            if key in self._mem:
                self.stats["mem_hits"] += 1
                return self._mem[key], "memory"
        compiled = self._disk_get(key)
        if compiled is not None:
            with self._lock:
                self._mem[key] = compiled
                self.stats["disk_hits"] += 1
            return compiled, "disk"
        self.stats["misses"] += 1
        return None, None

    def put(self, key: str, compiled, spill: bool = True) -> None:
        with self._lock:
            self._mem[key] = compiled
        if spill:
            self._disk_put(key, compiled)

    def compile(self, fn: Callable, example_args: tuple, *,
                key_fn: Optional[Callable] = None,
                mesh: Optional[jax.sharding.Mesh] = None,
                in_shardings: Any = None,
                donate_argnums: tuple = (),
                extras: tuple = ()):
        """AOT-compile ``fn`` for the signature of ``example_args``.

        ``key_fn`` fingerprints the cache entry when ``fn`` is a transform
        wrapper (e.g. a vmap of the user function) whose own source is not
        distinguishing. -> (compiled, source), source in
        {"memory","disk","compiled"}.
        """
        avals = abstractify(tuple(example_args))
        key = fingerprint(key_fn if key_fn is not None else fn, avals,
                          mesh=mesh,
                          extras=tuple(extras) + (bool(donate_argnums),
                                                  str(in_shardings)))
        compiled, source = self.get(key)
        if compiled is not None:
            return compiled, source
        kwargs = {}
        if in_shardings is not None:
            kwargs["in_shardings"] = in_shardings
        if donate_argnums:
            kwargs["donate_argnums"] = donate_argnums
        t0 = time.perf_counter()
        compiled = jax.jit(fn, **kwargs).lower(*avals).compile()
        with self._lock:
            self.stats["compile_s"] += time.perf_counter() - t0
        self.put(key, compiled)
        return compiled, "compiled"


_default_cache: Optional[CompileCache] = None
_default_lock = threading.Lock()


def default_cache() -> CompileCache:
    """Process-wide shared cache (launcher + serve use the same one)."""
    global _default_cache
    with _default_lock:
        if _default_cache is None:
            _default_cache = CompileCache()
        return _default_cache
