"""AOT executable cache — compile once, restart warm.

Every XLA backend compile costs from a tenth of a second to tens of
seconds (chip_smoke.py reports them); a server with a 4-rung ladder and
several apps pays that cold-start cost on every restart unless the
compiled artifact outlives the process.  This cache persists each
``jit(...).trace(...).lower().compile()`` result to disk via
``jax.experimental.serialize_executable`` and loads it back with
``deserialize_and_load`` — which performs NO backend compile (pinned by
tests/test_serve.py with CompileWatch), so a warm restart answers its
first request with zero compiles.

Keys bind the artifact to everything that could invalidate it:

- ``jax.__version__`` (serialized executables are not stable across
  releases),
- the topology (platform + device kinds + device count — an executable
  compiled for 8 sim-CPU devices must not load on a v5e),
- the batch shape signature (every input aval, so model shapes AND the
  ladder rung participate),
- the program name the caller passes, which the server builds from the
  app plus the engine's ``cache_tag()`` — options that are baked into
  the compiled program as constants (mfsgd's ``topk``, lda's
  ``em_iters``/``alpha``) shape the executable without changing any
  aval, so they must key separately or a restart with different flags
  would silently serve the old program,
- a code fingerprint (sha1 over the serve package sources plus the
  engine's model module — a changed step function must miss, never
  silently serve stale code).

Entries are atomic-rename pickle files (a process can be killed
mid-write, and a truncated entry must never poison later restarts).
A corrupt or stale entry falls back to a fresh compile — the cache can
lose, never lie.

Memory sidecar (PR 19): each entry persists its ``memory_analysis()``
HBM footprint (argument/output/temp/generated-code bytes) beside the
pickle as ``aot_<key>.mem.json`` — the literal input the multi-tenant
"does tenant N fit" admission check needs, surfaced on ``serve
--bench`` rows as ``exec_hbm_bytes`` and recorded on the memrec spine
(``kind:"memory"`` executable rows) on both the compile and the warm
cache-hit path.  Backends that do not expose the analysis (some CPU
sims) simply skip the sidecar — the footprint can be absent, never
wrong.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import warnings

import jax


def _topology_tag() -> str:
    devs = jax.devices()
    kinds = sorted({d.device_kind for d in devs})
    return f"{jax.default_backend()}:{len(devs)}:{','.join(kinds)}"


def code_fingerprint(extra_modules: tuple = ()) -> str:
    """sha1 over the serve package sources (+ any engine model modules):
    the executable is a compilation of this code, so the key must change
    when it does.  The parallel layer is always included — the sharded
    step programs compile through shard_map and the collective verbs, so
    a semantic change there must also miss."""
    import harp_tpu.parallel.collective as _coll
    import harp_tpu.parallel.mesh as _mesh
    import harp_tpu.serve as pkg

    h = hashlib.sha1()
    paths = []
    pkg_dir = os.path.dirname(os.path.abspath(pkg.__file__))
    for fn in sorted(os.listdir(pkg_dir)):
        if fn.endswith(".py"):
            paths.append(os.path.join(pkg_dir, fn))
    for mod in (_coll, _mesh) + tuple(extra_modules):
        f = getattr(mod, "__file__", None)
        if f and f.endswith(".py"):
            paths.append(f)
    for p in paths:
        with open(p, "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()[:16]


def _aval_sig(args) -> str:
    parts = []
    for a in jax.tree.leaves(args):
        shape = tuple(getattr(a, "shape", ()))
        dtype = getattr(a, "dtype", None)
        parts.append(f"{shape}/{dtype}")
    return ";".join(parts)


class ExecutableCache:
    """Disk-backed cache of serialized XLA executables.

    ``get_or_compile(name, jitted, args)`` returns a loaded executable:
    on a hit it deserializes (0 compiles); on a miss it compiles, then
    persists.  ``hits``/``misses`` count per instance so server startup
    can report cache effectiveness next to the CompileWatch delta.
    """

    def __init__(self, cache_dir: str, fingerprint: str | None = None):
        self.cache_dir = os.path.abspath(cache_dir)
        os.makedirs(self.cache_dir, exist_ok=True)
        self.fingerprint = fingerprint or code_fingerprint()
        self.hits = 0
        self.misses = 0

    def _key(self, name: str, args) -> str:
        sig = "|".join([name, jax.__version__, _topology_tag(),
                        self.fingerprint, _aval_sig(args)])
        return hashlib.sha1(sig.encode()).hexdigest()[:24]

    def _path(self, key: str) -> str:
        return os.path.join(self.cache_dir, f"aot_{key}.pkl")

    def _mem_path(self, key: str) -> str:
        return os.path.join(self.cache_dir, f"aot_{key}.mem.json")

    def footprint(self, name: str, args) -> dict | None:
        """The persisted memory_analysis() footprint for (name, arg
        shapes), or None (pre-PR-19 entry / backend without the
        analysis).  Read-only — admission checks call this without
        loading the executable."""
        try:
            with open(self._mem_path(self._key(name, args))) as fh:
                fp = json.load(fh)
            return fp if isinstance(fp, dict) else None
        except (OSError, ValueError):
            return None

    def load(self, name: str, args):
        """The cached executable for (name, arg shapes), or None."""
        from jax.experimental import serialize_executable

        key = self._key(name, args)
        path = self._path(key)
        try:
            with open(path, "rb") as fh:
                payload = pickle.load(fh)
            ser, in_tree, out_tree = payload
            exe = serialize_executable.deserialize_and_load(
                ser, in_tree, out_tree)
        except Exception as e:  # noqa: BLE001 — any bad entry (truncated
            # pickle, jaxlib XlaRuntimeError on a payload the key didn't
            # invalidate, ...) must degrade to a fresh compile: the cache
            # can lose, never lie — and never crash startup
            if os.path.exists(path):
                warnings.warn(
                    f"serve cache entry {os.path.basename(path)} "
                    f"unreadable ({type(e).__name__}: {e}) — recompiling",
                    RuntimeWarning)
            return None
        self.hits += 1
        from harp_tpu.utils import memrec

        fp = self.footprint(name, args) \
            or memrec.footprint_from_analysis(exe)
        memrec.note_executable(name, fp, source="cache")
        return exe

    def compile_and_store(self, name: str, jitted, args):
        from jax.experimental import serialize_executable

        with warnings.catch_warnings():
            # CPU XLA cannot honor buffer donation and warns per compile;
            # the donation is real on TPU (the double-buffer contract) and
            # harmlessly ignored on the sim backend
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            exe = jitted.trace(*args).lower().compile()
        self.misses += 1
        payload = serialize_executable.serialize(exe)
        key = self._key(name, args)
        path = self._path(key)
        tmp = f"{path}.{os.getpid()}.tmp"
        try:
            with open(tmp, "wb") as fh:
                pickle.dump(payload, fh)
            os.replace(tmp, path)
        except (OSError, pickle.PicklingError) as e:
            warnings.warn(f"serve cache write failed ({e}) — executable "
                          "stays in-memory only", RuntimeWarning)
            try:
                os.unlink(tmp)
            except OSError:
                pass
        from harp_tpu.utils import memrec

        fp = memrec.footprint_from_analysis(exe)
        if fp is not None:
            mem_path = self._mem_path(key)
            mem_tmp = f"{mem_path}.{os.getpid()}.tmp"
            try:
                with open(mem_tmp, "w") as fh:
                    json.dump(fp, fh)
                os.replace(mem_tmp, mem_path)
            except OSError:
                try:
                    os.unlink(mem_tmp)
                except OSError:
                    pass
        memrec.note_executable(name, fp, source="compile")
        return exe

    def get_or_compile(self, name: str, jitted, args):
        exe = self.load(name, args)
        if exe is None:
            exe = self.compile_and_store(name, jitted, args)
        return exe
