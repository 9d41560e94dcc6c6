"""Comm ledger + span tracer — one telemetry spine for the collective layer.

Reference parity (SURVEY.md §6): Harp's observability is log4j iteration
logs plus whatever byte counters Netty exposes per socket; nothing ties "how
many bytes did allreduce move this run" to the app's phases.  TACCL-style
communication *sketches* (PAPERS.md) — structured accounting of which
collectives move how much — are the prerequisite for optimizing them, and
the quantized-wire verbs (`allreduce_quantized`, `push_quantized`) make
EQuARX-style bandwidth claims this module lets a run audit.

Three cooperating pieces:

**CommLedger** — every verb in :mod:`harp_tpu.parallel.collective` calls
:func:`record_comm` at *trace time* (the only time Python runs inside
``shard_map``/jit).  One entry per call site records verb, axis, combiner,
wire dtype, and the per-shard payload bytes summed over the pytree — byte
math comes from ``aval.shape``/``dtype`` only, never per-element work.
Because a cached executable never re-runs Python, trace-time byte counts
must be multiplied by a *host-side execution counter*: wrap each jitted
invocation in :meth:`CommLedger.run` with ``steps`` = how many times the
traced sites execute per program run (epochs of a multi-epoch scan, iters
of a ``fori_loop``, reps of a bench loop).

Re-trace/cache semantics are explicit: each ``run()`` activation opens a
new *generation*; records landing in a generation overwrite (not add to)
the same call site's bytes from earlier generations, and per-execution
volume sums only the most recent generation that recorded anything.  So a
re-traced program (new jit wrapper, same sites) does not double-count, a
cached executable keeps its last traced byte sheet, and a Python chunk loop
hitting one site several times within a single trace still sums correctly.

**SpanTracer** — nested host-level phase spans
(``with span("epoch"): ...``) with JSONL export.  Spans interoperate with
the existing tools: each enabled span also enters
``jax.profiler.TraceAnnotation`` (so host phases show on the XLA trace
timeline next to :func:`harp_tpu.utils.profiling.annotate` regions), and
:meth:`SpanTracer.summary` returns the same ``{name: {mean_s, total_s, n}}``
shape as :class:`harp_tpu.utils.timing.Timer.summary`, so report code can
merge both; :meth:`SpanTracer.durations` is the query a reader uses
(by name, ancestor and absolute ``perf_counter`` time).

**ScopeMap** (``scopes``) — which part of the program each device op
belongs to, under the program's own names.  The step programs put
``jax.named_scope("<app>.<part>")`` around their parts; XLA carries the
name stack into every optimized instruction's ``op_name``, fusions
included, and a profiler trace names a device op by that instruction.
The map ``{HLO module: {instruction: op_name}}`` is read from a tracked
program's optimized HLO text (fed from ``flightrec.track``'s wrapper on
the program's first call with telemetry on), so a trace reducer can put
``fusion.396`` down to ``subgraph.sum.t3/subgraph.tail``
(``perf/scope_reduce.py``, which also says what in an ``op_name`` is a
scope: a lower-case segment with a dot, as no JAX primitive has).

Everything is **zero-cost when disabled** (the default): ``record_comm``
returns before touching the tree, ``span`` yields without bookkeeping, and
neither ever does per-element work — so telemetry can stay on for
measurement runs without perturbing BENCH numbers.  Enable with ``HARP_TELEMETRY=1``
in the environment or :func:`enable` in code; ``HARP_TELEMETRY_OUT=<path>``
makes instrumented CLIs export the raw JSONL for ``python -m harp_tpu
report``.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import re
import sys
import time
import weakref
from typing import Any

_ENABLED = os.environ.get("HARP_TELEMETRY", "0").lower() not in (
    "", "0", "off", "false")


def enabled() -> bool:
    """Is telemetry collection on? (module flag; see :func:`enable`)."""
    return _ENABLED


def enable(on: bool = True) -> None:
    """Turn collection on/off process-wide (tests use :func:`scope`)."""
    global _ENABLED
    _ENABLED = bool(on)


@contextlib.contextmanager
def scope(on: bool = True, *, reset: bool = True):
    """Enable (or disable) telemetry within a block, restoring the prior
    flag on exit; ``reset`` clears every collector (ledger, tracer, scope
    map and the flight recorder) on entry so a test sees only its own
    records."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(on)
    if reset:
        ledger.reset()
        tracer.reset()
        scopes.reset()
        from harp_tpu import elastic, health
        from harp_tpu.utils import (flightrec, memrec, reqtrace, skew,
                                    steptrace)

        flightrec.reset()
        skew.reset()
        reqtrace.reset()
        health.reset()
        elastic.reset()
        steptrace.reset()
        memrec.reset()
    try:
        yield
    finally:
        _ENABLED = prev


def budget(**kw):
    """``with telemetry.budget(compiles=1, readbacks=1): ...`` — the
    flight recorder's budget guard (see :func:`harp_tpu.utils.flightrec.
    budget` for the counter semantics and the raise/warn actions)."""
    from harp_tpu.utils import flightrec

    return flightrec.budget(**kw)


def out_path() -> str | None:
    """Export destination for instrumented CLIs (``HARP_TELEMETRY_OUT``)."""
    return os.environ.get("HARP_TELEMETRY_OUT") or None


# ---------------------------------------------------------------------------
# CommLedger
# ---------------------------------------------------------------------------

_UNTAGGED = "(untagged)"


def _tree_wire_bytes(tree: Any, wire_dtype: Any | None) -> tuple[int, int]:
    """(payload_bytes, n_leaves) for one verb call, per shard.

    Bytes come from static shape/dtype only.  With a ``wire_dtype``, float
    leaves are accounted at the wire format's width — the verb's *logical*
    wire (the int8 wire accounts 1 byte/element even though the current
    lowering accumulates the psum in int32); non-float leaves ride exact at
    their own width, matching the quantized verbs' exact path.
    """
    import jax
    import jax.numpy as jnp

    import numpy as np

    wd = None if wire_dtype is None else jnp.dtype(wire_dtype)
    total = 0
    leaves = jax.tree.leaves(tree)
    for x in leaves:
        # leaves are usually tracers/arrays; Python scalars (a bare float
        # pushed through a verb) still account at their promoted dtype
        dt = jnp.dtype(getattr(x, "dtype", None) or jnp.result_type(x))
        size = 1
        for s in getattr(x, "shape", np.shape(x)):
            size *= int(s)
        if wd is not None and jnp.issubdtype(dt, jnp.floating):
            dt = wd
        total += size * dt.itemsize
    return total, len(leaves)


def is_ledger_user_frame(filename: str) -> bool:
    """Is an (absolute) source filename a *user* frame for collective
    call-site attribution?  Shared by :func:`record_comm`'s trace-time
    site keys and the static CommGraph matcher
    (:mod:`harp_tpu.analysis.commgraph`), which must derive the SAME key
    from a jaxpr eqn's traceback or the HL301/HL302 site matching would
    compare apples to oranges.  Excluded: this module, the collective
    verb layer, anything under the jax package, and contextlib glue."""
    import jax

    jax_dir = os.path.dirname(os.path.abspath(jax.__file__))
    here = os.path.abspath(__file__)
    return (filename != here
            and not filename.endswith("parallel/collective.py")
            and not filename.startswith(jax_dir)
            and "contextlib" not in os.path.basename(filename))


def site_key(filename: str, lineno: int) -> str:
    """The ledger's call-site key shape: ``basename.py:lineno``."""
    return f"{os.path.basename(filename)}:{lineno}"


def _call_site() -> str:
    """Stable key for the user frame that invoked the verb: the nearest
    stack frame outside this module, the collective module, and the jax
    package (jit/shard_map tracing interposes jax frames between the
    verb and the user's code)."""
    f = sys._getframe(1)
    while f is not None:
        fn = os.path.abspath(f.f_code.co_filename)
        if is_ledger_user_frame(fn):
            return site_key(fn, f.f_lineno)
        f = f.f_back
    return "?:0"


class CommLedger:
    """Per-call-site collective byte accounting (see module docstring)."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        # tag -> {"gen", "last_record_gen", "executions", "sites"}
        # sites: (site, verb, axis, combiner, wire) -> record dict
        self._tags: dict[str, dict] = {}
        self._tag_stack: list[str] = []

    # -- recording (trace time) --------------------------------------------
    def record(self, verb: str, tree: Any, *, axis: str,
               combiner: str | None = None,
               wire_dtype: Any | None = None) -> None:
        if not _ENABLED:
            return
        payload, n_leaves = _tree_wire_bytes(tree, wire_dtype)
        import jax.numpy as jnp

        wire = None if wire_dtype is None else jnp.dtype(wire_dtype).name
        site = _call_site()
        tag = self._tag_stack[-1] if self._tag_stack else _UNTAGGED
        t = self._tags.setdefault(
            tag, {"gen": 0, "last_record_gen": 0, "executions": 0,
                  "sites": {}})
        key = (site, verb, axis, combiner, wire)
        rec = t["sites"].get(key)
        if rec is None or rec["gen"] != t["gen"]:
            # first record for this site in this generation: a re-trace of
            # a cached program overwrites its old sheet instead of adding
            rec = {"site": site, "verb": verb, "axis": axis,
                   "combiner": combiner, "wire_dtype": wire,
                   "payload_bytes": 0, "calls_per_trace": 0,
                   "leaves": n_leaves, "gen": t["gen"]}
            t["sites"][key] = rec
        rec["payload_bytes"] += payload
        rec["calls_per_trace"] += 1
        rec["leaves"] = n_leaves
        t["last_record_gen"] = t["gen"]

    # -- execution counting (host side) ------------------------------------
    @contextlib.contextmanager
    def run(self, tag: str, *, steps: int = 1):
        """Attribute trace-time records inside the block to ``tag`` and
        count ``steps`` executions of its traced sites.

        ``steps`` is how many times the sites recorded under this tag
        execute during the block: the epoch count of a multi-epoch scan,
        the ``fori_loop`` trip count, the rep count of a bench loop —
        ``steps=0`` attributes a trace without counting executions (AOT
        ``.lower().compile()`` warmup).
        """
        if not _ENABLED:
            yield self
            return
        t = self._tags.setdefault(
            tag, {"gen": 0, "last_record_gen": 0, "executions": 0,
                  "sites": {}})
        t["gen"] += 1
        self._tag_stack.append(tag)
        try:
            yield self
        finally:
            self._tag_stack.pop()
            t["executions"] += int(steps)

    # -- reading ------------------------------------------------------------
    def _live_sites(self, t: dict) -> list[dict]:
        g = t["last_record_gen"]
        return [r for r in t["sites"].values() if r["gen"] == g]

    def bytes_per_execution(self, tag: str) -> int:
        t = self._tags.get(tag)
        return 0 if t is None else sum(
            r["payload_bytes"] for r in self._live_sites(t))

    def executions(self, tag: str) -> int:
        t = self._tags.get(tag)
        return 0 if t is None else t["executions"]

    def volume(self, tag: str | None = None) -> int:
        """Total comm bytes: per-execution bytes × executions (one tag, or
        summed over all tags when ``tag`` is None; untagged sites have no
        execution counter and contribute their per-trace bytes once)."""
        tags = [tag] if tag is not None else list(self._tags)
        total = 0
        for name in tags:
            t = self._tags.get(name)
            if t is None:
                continue
            per = sum(r["payload_bytes"] for r in self._live_sites(t))
            total += per * (t["executions"] if name != _UNTAGGED
                            else max(1, t["executions"]))
        return total

    def summary(self) -> dict:
        """Machine-readable ledger: one entry per tag with live sites."""
        out = {}
        for name, t in sorted(self._tags.items()):
            sites = [
                {k: r[k] for k in ("site", "verb", "axis", "combiner",
                                   "wire_dtype", "payload_bytes",
                                   "calls_per_trace", "leaves")}
                for r in sorted(self._live_sites(t),
                                key=lambda r: -r["payload_bytes"])]
            out[name] = {
                "executions": t["executions"],
                "bytes_per_execution": sum(s["payload_bytes"]
                                           for s in sites),
                "total_bytes": self.volume(name),
                "sites": sites,
            }
        return out

    def export_jsonl(self, fh) -> None:
        for tag, t in sorted(self._tags.items()):
            for r in self._live_sites(t):
                row = {"kind": "comm", "tag": tag,
                       "executions": t["executions"]}
                row.update({k: r[k] for k in (
                    "site", "verb", "axis", "combiner", "wire_dtype",
                    "payload_bytes", "calls_per_trace", "leaves")})
                fh.write(json.dumps(row) + "\n")


# ---------------------------------------------------------------------------
# SpanTracer
# ---------------------------------------------------------------------------

class SpanTracer:
    """Nested host-level spans with JSONL export (see module docstring)."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self._t0 = time.perf_counter()
        self._stack: list[str] = []
        self.records: list[dict] = []

    def current_path(self) -> str | None:
        """The live span path ("epoch/ingest"), or None outside any span —
        the flight recorder stamps compile/transfer records with this."""
        return "/".join(self._stack) or None

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any):
        """``with span("epoch"): ...`` — records {span, path, t0, dur,
        depth} plus any ``attrs``; nesting comes from the live stack.  Also
        enters ``jax.profiler.TraceAnnotation(name)`` so the phase shows on
        an XLA trace captured by :func:`harp_tpu.utils.profiling.trace`.
        An enabled span yields its ``attrs`` dict (``with span(...) as a``):
        what is known only after the work is put there before the exit; a
        disabled one yields ``None``."""
        if not _ENABLED:
            yield
            return
        import jax

        path = "/".join(self._stack + [name])
        depth = len(self._stack)
        self._stack.append(name)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield attrs
        finally:
            dur = time.perf_counter() - t0
            self._stack.pop()
            rec = {"span": name, "path": path,
                   "t0": round(t0 - self._t0, 6),
                   "dur": round(dur, 6), "depth": depth}
            if attrs:
                rec.update(attrs)
            self.records.append(rec)

    def durations(self, name: str, under: str | None = None,
                  t0: float = -math.inf, t1: float = math.inf) -> list[float]:
        """Seconds of every recorded span ``name`` that lies inside
        ``[t0, t1]`` (absolute ``time.perf_counter`` seconds) and, with
        ``under``, has a span of that name among its ancestors."""
        return [r["dur"] for r in self.records
                if r["span"] == name
                and (under is None or under in r["path"].split("/")[:-1])
                and self._t0 + r["t0"] >= t0
                and self._t0 + r["t0"] + r["dur"] <= t1]

    def summary(self) -> dict[str, dict[str, float]]:
        """Per-name aggregate in :meth:`Timer.summary`'s shape, so span and
        timer tables merge in the run report."""
        agg: dict[str, list[float]] = {}
        for r in self.records:
            agg.setdefault(r["span"], []).append(r["dur"])
        return {
            k: {"mean_s": sum(v) / len(v), "total_s": sum(v), "n": len(v)}
            for k, v in agg.items()
        }

    def export_jsonl(self, fh) -> None:
        for r in self.records:
            fh.write(json.dumps({"kind": "span", **r}) + "\n")


# ---------------------------------------------------------------------------
# ScopeMap
# ---------------------------------------------------------------------------

_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CACHE_KEY_METADATA = "jax_compilation_cache_include_metadata_in_key"


@contextlib.contextmanager
def current_names():
    """Compile with the names in the persistent cache's key while
    telemetry is on.  JAX leaves ``op_name`` and source lines out of that
    key, so a cache filled by another version of the source hands back an
    executable that carries THAT version's names (measured: a program
    compiled without its scopes, then with them, was a hit and its text
    had none).  A program whose map is read is compiled, or looked up,
    under a key that holds them.  Nothing changes with telemetry off."""
    if not _ENABLED:
        yield
        return
    import jax

    before = getattr(jax.config, _CACHE_KEY_METADATA)
    jax.config.update(_CACHE_KEY_METADATA, True)
    try:
        yield
    finally:
        jax.config.update(_CACHE_KEY_METADATA, before)


class ScopeMap:
    """``{HLO module name: {instruction name: op_name}}`` of the tracked
    programs (see module docstring)."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.modules: dict[str, dict[str, str]] = {}
        self.labels: dict[str, str] = {}    # module -> track() label
        self.skipped: dict[str, str] = {}   # label -> why it gave no map
        self._seen = weakref.WeakSet()

    def record(self, tracked: Any, label: str, fn: Any, args: tuple,
               kw: dict) -> bool:
        """Read the map of the program ``fn`` that ``tracked`` wraps, once
        per tracked object whatever it gives; ``True`` when this call read
        it.  An AOT ``Compiled`` gives its text as it is; a ``jax.jit`` is
        lowered for these arguments (only their avals are read: donated
        ones are safe) and compiled under :func:`current_names`, so that
        the dispatch that follows under the same finds it in the
        persistent cache where there is one (the compile watch then counts
        the map's compile and the load; without a cache, two compiles: a
        ``budget(compiles=...)`` around a tracked program's FIRST call with
        telemetry on has to allow for the map's).  A
        callable that is neither, or whose text cannot be had, is counted
        in ``skipped`` with the reason: the map never raises into a
        dispatch."""
        if tracked in self._seen:
            return False
        self._seen.add(tracked)
        self._read(label, fn, args, kw)
        return True

    def _read(self, label: str, fn: Any, args: tuple, kw: dict) -> None:
        try:
            if hasattr(fn, "lower"):
                with current_names():
                    text = fn.lower(*args, **kw).compile().as_text()
            elif hasattr(fn, "as_text"):
                text = fn.as_text()
            else:
                self.skipped[label] = "neither a jax.jit nor a Compiled"
                return
        except Exception as e:  # noqa: BLE001 - see the docstring
            self.skipped[label] = f"{type(e).__name__}: {e}"[:200]
            return
        if not self.add_text(text or "", label):
            self.skipped[label] = "no HloModule line in its text"

    def add_text(self, hlo_text: str, label: str | None = None) -> bool:
        """Parse one optimized HLO module's text into the map; a second
        program of the same module name adds to the first's entries."""
        head = _HLO_MODULE.match(hlo_text)
        if head is None:
            return False
        instructions = self.modules.setdefault(head.group(1), {})
        if label is not None:
            self.labels[head.group(1)] = label
        for line in hlo_text.splitlines():
            name = _HLO_INSTRUCTION.match(line)
            if name is None:
                continue
            op_name = _HLO_OP_NAME.search(line, name.end())
            if op_name is not None:
                instructions[name.group(1)] = op_name.group(1)
        return True

    def lookup(self, module: str, instruction: str) -> str | None:
        return self.modules.get(module, {}).get(instruction)

    def summary(self) -> dict:
        """``{"modules": {module: {"label", "instructions"}}, "skipped":
        {label: reason}}``; ``instructions`` counts those with an
        ``op_name``."""
        return {"modules": {m: {"label": self.labels.get(m),
                                "instructions": len(i)}
                            for m, i in sorted(self.modules.items())},
                "skipped": dict(self.skipped)}

    def export_jsonl(self, fh) -> None:
        for module, instructions in sorted(self.modules.items()):
            for name, op_name in instructions.items():
                fh.write(json.dumps({
                    "kind": "scope", "module": module,
                    "label": self.labels.get(module),
                    "instruction": name, "op_name": op_name}) + "\n")


# ---------------------------------------------------------------------------
# Module singletons + the verbs' hook
# ---------------------------------------------------------------------------

ledger = CommLedger()
tracer = SpanTracer()
scopes = ScopeMap()


def span(name: str, **attrs: Any):
    """Module-level shorthand for ``tracer.span`` (the common import)."""
    return tracer.span(name, **attrs)


def record_comm(verb: str, tree: Any, *, axis: str,
                combiner: str | None = None,
                wire_dtype: Any | None = None) -> None:
    """The one hook the collective verbs call (trace time only)."""
    if not _ENABLED:
        return
    ledger.record(verb, tree, axis=axis, combiner=combiner,
                  wire_dtype=wire_dtype)
    from harp_tpu.utils import steptrace

    if steptrace.tracer._run is not None:
        steptrace.tracer.on_comm(verb, _call_site())


def export(path: str) -> None:
    """Write every collected record (spans + ledger + scope map + flight
    recorder + skew ledger + request traces + health findings + elastic
    actions + memory ledger) as one JSONL file — the input format of
    ``python -m harp_tpu report``, ``python -m harp_tpu trace``,
    ``python -m harp_tpu timeline``, ``python -m harp_tpu health``, and
    ``python -m harp_tpu memory``."""
    from harp_tpu import elastic, health
    from harp_tpu.utils import (flightrec, memrec, reqtrace, skew,
                                steptrace)

    with open(path, "w") as fh:
        tracer.export_jsonl(fh)
        ledger.export_jsonl(fh)
        scopes.export_jsonl(fh)
        flightrec.export_jsonl(fh)
        skew.export_jsonl(fh)
        reqtrace.tracer.export_jsonl(fh)
        health.export_jsonl(fh)
        elastic.export_jsonl(fh)
        steptrace.export_jsonl(fh)
        memrec.export_jsonl(fh)


def export_timeline(path: str) -> None:
    """Merge EVERY spine into one causally-ordered ``kind:"trace"``
    JSONL (PR 12) — request spans + batch records + fault-plane marks
    (already timestamped trace rows), host spans (ts = span t0) and XLA
    compiles (ts = the compile's wall offset on the span clock) folded
    in as marks, and the timestamp-less aggregate spines (comm ledger,
    transfer sites, skew phases) appended at the end as ``summary``
    rows riding the final timestamp — they describe the whole run, so
    the causal slot they occupy is "after everything".

    Clock domains are normalized per source to its own origin (the
    serve replay drives a virtual clock; spans/compiles ride the
    SpanTracer's wall offset), so ordering is exact within a source and
    aligned-at-zero across sources.  The output passes
    scripts/check_jsonl.py invariant 11 and loads in
    ``python -m harp_tpu trace`` / Perfetto via :func:`harp_tpu.utils.
    reqtrace.perfetto`.

    Training-plane spans (PR 18): any collected ``kind:"steptrace"``
    rows ride the same file after the trace rows, unmodified (they are
    already one causal block on the SpanTracer clock and pass
    invariant 16 as exported) — ``python -m harp_tpu timeline`` reads
    them out of the merged file directly.
    """
    from harp_tpu.utils import flightrec, reqtrace, skew, steptrace

    def _normalized(rows: list[dict]) -> list[dict]:
        if not rows:
            return []
        t0 = min(float(r["ts"]) for r in rows)
        return [{**r, "ts": round(float(r["ts"]) - t0, 6)} for r in rows]

    rows = _normalized(reqtrace.tracer.rows())
    host: list[dict] = [
        {"kind": "trace", "ev": "mark", "source": "span", "ts": r["t0"],
         "name": r["span"], "path": r["path"], "dur": r["dur"],
         "depth": r["depth"]}
        for r in tracer.records]
    host += [
        {"kind": "trace", "ev": "mark", "source": "compile",
         "ts": r.get("t", 0.0), "name": "backend_compile",
         "dur": r["dur"], "span": r["span"]}
        for r in flightrec.compile_watch.records]
    rows += _normalized(host)
    rows.sort(key=lambda r: r["ts"])
    t_end = rows[-1]["ts"] if rows else 0.0
    for tag, t in sorted(ledger.summary().items()):
        rows.append({"kind": "trace", "ev": "summary", "source": "comm",
                     "ts": t_end, "name": tag,
                     "executions": t["executions"],
                     "total_bytes": t["total_bytes"]})
    tr = flightrec.transfers.summary()
    if tr["sites"]:
        rows.append({"kind": "trace", "ev": "summary",
                     "source": "transfer", "ts": t_end, "name": "totals",
                     "h2d_bytes": tr["h2d_bytes"],
                     "dispatches": tr["dispatches"],
                     "readbacks": tr["readbacks"]})
    for phase, s in skew.ledger.summary().items():
        rows.append({"kind": "trace", "ev": "summary", "source": "skew",
                     "ts": t_end, "name": phase,
                     "max_mean_ratio": s.get("max_mean_ratio")})
    stamp = flightrec.provenance_stamp()
    with open(path, "w") as fh:
        for row in rows:
            fh.write(json.dumps({**row, **stamp}) + "\n")
        steptrace.tracer.export_jsonl(fh, stamp)


def load_rows(path: str) -> dict[str, list[dict]]:
    """Read an :func:`export` file back, keyed by record kind:
    ``{"span": [...], "comm": [...], "compile": [...], "transfer":
    [...], "skew": [...], "trace": [...], "health": [...],
    "elastic": [...], "steptrace": [...], "memory": [...], "scope":
    [...]}`` (unknown
    kinds land under ``"comm"`` for backward compatibility with
    pre-flight-recorder exports, whose only unmarked rows were the
    ledger's)."""
    out: dict[str, list[dict]] = {"span": [], "comm": [], "compile": [],
                                  "transfer": [], "skew": [],
                                  "trace": [], "health": [],
                                  "elastic": [], "steptrace": [],
                                  "memory": [], "scope": []}
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            kind = row.get("kind")
            out[kind if kind in out else "comm"].append(row)
    return out


def load_jsonl(path: str) -> tuple[list[dict], list[dict]]:
    """Back-compat loader: (span rows, comm rows) only."""
    rows = load_rows(path)
    return rows["span"], rows["comm"]
