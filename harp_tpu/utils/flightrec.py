"""Execution flight recorder — compile / transfer / dispatch telemetry
with enforceable budget guards.

Reference parity (SURVEY.md §6): Harp has no execution-side accounting at
all — its observability stops at per-iteration wall-clock logs, and even
harp-tpu's CommLedger (PR 1) only accounts for *collective* bytes.  Yet
the costs a driver loop can add without changing one collective are
execution-side: a silent recompile, a host→device re-upload, a
dispatch/readback round trip per epoch.  This module is the third
telemetry spine beside CommLedger/SpanTracer, turning each of those
into a machine-checked invariant that runs on the CPU backend with
zero hardware:

**CompileWatch** — subscribes to ``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration`` event (fired for every
XLA backend compile) and records
count, duration, and the active :class:`~harp_tpu.utils.telemetry.
SpanTracer` span — so a recompile inside a timed region is *detected*,
not re-derived by hand from wall-clock anomalies.

**TransferLedger** — counts H2D/D2H bytes and dispatch round trips per
call site and active span.  The project's transfer entry points feed it:
``WorkerMesh.shard_array``/``shard_array_local`` (H2D), :func:`readback`
and ``timing.device_sync`` (blocking D2H round trips), :func:`track`-
wrapped jitted callables (dispatches), and
``dispatch.bucket_by_destination`` (trace-time exchange-buffer bytes).

**budget()** — ``with flightrec.budget(compiles=1, readbacks=1): ...``
snapshots the counters and, on exit, raises :class:`BudgetExceeded`
(tests) or warns (bench, ``action="warn"``) when a delta exceeds its
bound.  The CLAUDE.md traps map directly: ``compiles=N`` catches
PRNGKey-specialization recompiles, ``readbacks=1`` catches per-epoch
readback loops, ``h2d_bytes=B`` catches re-uploading a resident table.

Everything shares the CommLedger's enable switch (``HARP_TELEMETRY=1`` /
``telemetry.enable()``) and its **zero-cost when disabled** contract:
every entry point returns before touching arrays or counters, byte math
comes from shape/dtype only, and no instrumentation ever adds a device
dispatch — the traced program is bit-identical with telemetry on or off
(tested in tests/test_flightrec.py).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import warnings
from typing import Any, Callable

from harp_tpu.utils import memrec, telemetry

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_PROV_FIELDS = ("backend", "date", "commit")


def _call_site() -> str:
    """Nearest user frame outside this module / the wrapped entry-point
    modules / jax — same contract as ``telemetry._call_site`` but skipping
    the transfer wrappers (mesh/timing/dispatch) instead of collective."""
    import jax

    jax_dir = os.path.dirname(os.path.abspath(jax.__file__))
    here = os.path.dirname(os.path.abspath(__file__))  # utils/
    skip_tails = ("parallel/mesh.py", "parallel/dispatch.py")
    f = sys._getframe(1)
    while f is not None:
        fn = os.path.abspath(f.f_code.co_filename)
        base = os.path.basename(fn)
        if (not fn.startswith(jax_dir)
                and not fn.endswith(skip_tails)
                and os.path.dirname(fn) != here
                and "contextlib" not in base):
            return f"{base}:{f.f_lineno}"
        f = f.f_back
    return "?:0"


# ---------------------------------------------------------------------------
# CompileWatch
# ---------------------------------------------------------------------------

class CompileWatch:
    """Every XLA backend compile, with duration and active span."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.count = 0
        self.total_s = 0.0
        self.records: list[dict] = []  # {"dur", "span", "t"} per compile

    def on_compile(self, duration: float) -> None:
        import time

        self.count += 1
        self.total_s += float(duration)
        # "t": completion offset on the SpanTracer's clock, so the
        # compile lands on telemetry.export_timeline next to the host
        # span it fired under (PR 12)
        self.records.append({"dur": round(float(duration), 6),
                             "span": telemetry.tracer.current_path(),
                             "t": round(time.perf_counter()
                                        - telemetry.tracer._t0, 6)})
        from harp_tpu.utils import steptrace

        if steptrace.tracer._run is not None:  # PR 18 superstep mark
            steptrace.tracer.on_compile(duration)

    def summary(self) -> dict:
        """{"count", "total_s", "by_span": {span_path: {count, total_s}}}."""
        by_span: dict[str, dict] = {}
        for r in self.records:
            s = by_span.setdefault(r["span"] or "(no span)",
                                   {"count": 0, "total_s": 0.0})
            s["count"] += 1
            s["total_s"] = round(s["total_s"] + r["dur"], 6)
        return {"count": self.count, "total_s": round(self.total_s, 6),
                "by_span": by_span}

    def export_jsonl(self, fh, stamp: dict | None = None) -> None:
        """One row per compile; ``count``/``total_s`` are CUMULATIVE so
        scripts/check_jsonl.py can enforce monotonicity (invariant 4)."""
        cum = 0.0
        for i, r in enumerate(self.records):
            cum = round(cum + r["dur"], 6)
            row = {"kind": "compile", "event": "backend_compile",
                   "count": i + 1, "dur": r["dur"], "total_s": cum,
                   "span": r["span"], "t": r.get("t"), **(stamp or {})}
            fh.write(json.dumps(row) + "\n")


def _on_monitoring_event(event: str, duration: float, **kw: Any) -> None:
    # registered once per process; the enabled() check keeps the listener
    # zero-cost for every un-instrumented run in the same process
    if event != _BACKEND_COMPILE_EVENT:
        return
    for cb in tuple(_COMPILE_OBSERVERS):
        cb("compile", duration)
    if telemetry.enabled():
        compile_watch.on_compile(duration)


def _on_cache_hit(event: str, **kw: Any) -> None:
    # a persistent-cache hit still fires the backend-compile event above
    # (with the short retrieval time); this says the seconds were a load
    if event == _CACHE_HIT_EVENT:
        for cb in tuple(_COMPILE_OBSERVERS):
            cb("cache_hit", 0.0)


def _install_compile_listener() -> None:
    """Subscribe to backend-compile and persistent-cache-hit events."""
    import jax.monitoring as monitoring

    monitoring.register_event_duration_secs_listener(_on_monitoring_event)
    monitoring.register_event_listener(_on_cache_hit)


# ---------------------------------------------------------------------------
# TransferLedger
# ---------------------------------------------------------------------------

class TransferLedger:
    """H2D/D2H bytes and dispatch round trips per (op, site, span).

    Ops: ``h2d`` (host→device placement), ``readback`` (blocking
    device→host fetch — the D2H path in this codebase is always a round
    trip), ``dispatch`` (one invocation of a :func:`track`-wrapped jitted
    callable), ``bucket`` (trace-time all_to_all exchange-buffer bytes
    staged by ``dispatch.bucket_by_destination`` — capacity slots ride
    the wire whether or not they carry items).
    """

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.h2d_bytes = 0
        self.h2d_calls = 0
        self.d2h_bytes = 0
        self.readbacks = 0
        self.dispatches = 0
        self.bucket_bytes = 0
        # (op, site, span) -> {"op","site","span","bytes","calls"}
        self._sites: dict[tuple, dict] = {}

    def _rec(self, op: str, nbytes: int, site: str | None) -> None:
        site = site or _call_site()
        span = telemetry.tracer.current_path()
        key = (op, site, span)
        r = self._sites.setdefault(
            key, {"op": op, "site": site, "span": span, "bytes": 0,
                  "calls": 0})
        r["bytes"] += int(nbytes)
        r["calls"] += 1

    def record_h2d(self, nbytes: int, site: str | None = None) -> None:
        self.h2d_bytes += int(nbytes)
        self.h2d_calls += 1
        self._rec("h2d", nbytes, site)

    def record_readback(self, nbytes: int = 0,
                        site: str | None = None) -> None:
        self.d2h_bytes += int(nbytes)
        self.readbacks += 1
        self._rec("readback", nbytes, site)

    def record_dispatch(self, site: str | None = None) -> None:
        self.dispatches += 1
        self._rec("dispatch", 0, site)

    def record_bucket(self, nbytes: int, site: str | None = None) -> None:
        self.bucket_bytes += int(nbytes)
        self._rec("bucket", nbytes, site)

    def summary(self) -> dict:
        sites = sorted(self._sites.values(),
                       key=lambda r: (-r["bytes"], r["op"], r["site"]))
        return {"h2d_bytes": self.h2d_bytes, "h2d_calls": self.h2d_calls,
                "d2h_bytes": self.d2h_bytes, "readbacks": self.readbacks,
                "dispatches": self.dispatches,
                "bucket_bytes": self.bucket_bytes,
                "sites": [dict(r) for r in sites]}

    def export_jsonl(self, fh, stamp: dict | None = None) -> None:
        for r in sorted(self._sites.values(),
                        key=lambda r: (r["op"], r["site"])):
            fh.write(json.dumps({"kind": "transfer", **r,
                                 **(stamp or {})}) + "\n")


# ---------------------------------------------------------------------------
# Module singletons + zero-cost entry points
# ---------------------------------------------------------------------------

compile_watch = CompileWatch()
transfers = TransferLedger()
_install_compile_listener()


def reset() -> None:
    """Clear both collectors (telemetry.scope does this on entry)."""
    compile_watch.reset()
    transfers.reset()


def record_h2d(nbytes: int, site: str | None = None) -> None:
    """Hook for host→device placement entry points (mesh.shard_array)."""
    if _H2D_OBSERVERS:
        for cb in tuple(_H2D_OBSERVERS):
            cb(nbytes, site)
    if telemetry.enabled():
        transfers.record_h2d(nbytes, site)
        memrec.on_staged(nbytes, site or _call_site())


def record_readback(nbytes: int = 0, site: str | None = None) -> None:
    """Hook for blocking device→host fetches (timing.device_sync)."""
    if telemetry.enabled():
        transfers.record_readback(nbytes, site)


def record_bucket(nbytes: int, site: str | None = None) -> None:
    """Trace-time hook for capacity-bucket staging (parallel.dispatch)."""
    if telemetry.enabled():
        transfers.record_bucket(nbytes, site)


# Observer hooks: audit/chaos layers watch the instrumented execution
# paths without riding the telemetry enable switch — the commgraph
# donation audit (HL303) watches readbacks to catch a host re-read of a
# donated buffer, and the fault plane (utils.fault.FaultInjector, PR 10)
# rides all four to fail/delay dispatch, H2D, readback, and
# checkpoint-write sites on a seeded schedule.  Every list is empty in an
# un-observed run, so the hot-path cost is one falsy check per event;
# observers see the ORIGINAL arguments (e.g. the device array, before
# np.asarray materializes it) and may raise — a raising observer aborts
# the observed operation BEFORE it is counted or performed, modeling a
# transient failure in flight.
_READBACK_OBSERVERS: list[Callable[[Any], None]] = []
_DISPATCH_OBSERVERS: list[Callable[[str], None]] = []
_H2D_OBSERVERS: list[Callable[[int, Any], None]] = []
_CKPT_WRITE_OBSERVERS: list[Callable[[str], None]] = []
_COMPILE_OBSERVERS: list[Callable[[str, float], None]] = []
_PROGRAM_OBSERVERS: list[Callable[[str, Callable, tuple, dict], None]] = []


@contextlib.contextmanager
def _observe(registry: list, cb: Callable):
    registry.append(cb)
    try:
        yield
    finally:
        registry.remove(cb)


def observe_readbacks(cb: Callable[[Any], None]):
    """Register ``cb`` to see every :func:`readback` argument within the
    block (the donation audit's hook; independent of the telemetry
    enable switch — an audit must see reads even with telemetry off)."""
    return _observe(_READBACK_OBSERVERS, cb)


def observe_dispatches(cb: Callable[[str], None]):
    """``cb(label)`` before every :func:`track`-wrapped dispatch — fired
    BEFORE the dispatch is counted or launched, so a raising observer
    models a dispatch that never reached the device (the counters stay
    exact: only launched dispatches count)."""
    return _observe(_DISPATCH_OBSERVERS, cb)


def observe_compiles(cb: Callable[[str, float], None]):
    """``cb("compile", seconds)`` for every XLA backend compile and
    ``cb("cache_hit", 0.0)`` for every persistent-cache hit (a hit also
    fires the compile event, with the retrieval time) — chip_smoke.py's
    compile-seconds meter."""
    return _observe(_COMPILE_OBSERVERS, cb)


def observe_programs(cb: Callable[[str, Callable, tuple, dict], None]):
    """``cb(label, fn, args, kwargs)`` before every :func:`track`-wrapped
    dispatch, with the wrapped callable itself — so an auditor can lower
    the very program about to run (chip_smoke.py proves a Mosaic call in
    it) without the driver handing its jitted function out."""
    return _observe(_PROGRAM_OBSERVERS, cb)


def observe_h2d(cb: Callable[[int, Any], None]):
    """``cb(nbytes, site)`` before every counted host→device placement
    (``mesh.shard_array``/``shard_array_local``)."""
    return _observe(_H2D_OBSERVERS, cb)


def observe_ckpt_writes(cb: Callable[[str], None]):
    """``cb(path)`` at the START of every ``CheckpointManager.save`` —
    before any byte lands on disk, so a raising observer models a crash
    mid-write (the atomic tmp-dir rename must make that unobservable to
    readers)."""
    return _observe(_CKPT_WRITE_OBSERVERS, cb)


def notify_ckpt_write(path: str) -> None:
    """Hook for checkpoint-write entry points (checkpoint.save)."""
    if _CKPT_WRITE_OBSERVERS:
        for cb in tuple(_CKPT_WRITE_OBSERVERS):
            cb(path)


def readback(x: Any):
    """``np.asarray(x)`` that counts the D2H round trip — THE instrumented
    device→host fetch for driver code (zero-cost ``np.asarray`` when
    telemetry is off)."""
    import numpy as np

    if _READBACK_OBSERVERS:
        for cb in tuple(_READBACK_OBSERVERS):
            cb(x)
    out = np.asarray(x)
    if telemetry.enabled():
        transfers.record_readback(out.nbytes)
    return out


class _Tracked:
    """:func:`track`'s wrapper — counts one dispatch per call, delegates
    every other attribute (``lower``, ``trace``, ...) to the wrapped
    callable so a tracked ``jax.jit`` keeps its full surface."""

    __slots__ = ("__wrapped__", "_label", "__weakref__")

    def __init__(self, fn: Callable, label: str):
        self.__wrapped__ = fn
        self._label = label

    def __call__(self, *args, **kw):
        if _PROGRAM_OBSERVERS:
            for cb in tuple(_PROGRAM_OBSERVERS):
                cb(self._label, self.__wrapped__, args, kw)
        if _DISPATCH_OBSERVERS:  # BEFORE counting: a raising observer
            for cb in tuple(_DISPATCH_OBSERVERS):  # models a dispatch
                cb(self._label)                    # that never launched
        if telemetry.enabled():
            transfers.record_dispatch(self._label)
            memrec.on_dispatch(self._label, args)
            if telemetry.scopes.record(self, self._label, self.__wrapped__,
                                       args, kw):
                # this program's first call with telemetry on: its op map
                # was read, and the dispatch goes under the same cache
                # key, so that a program not yet compiled is compiled once
                with telemetry.current_names():
                    out = self.__wrapped__(*args, **kw)
            else:
                out = self.__wrapped__(*args, **kw)
            memrec.on_output(self._label, out)
            return out
        return self.__wrapped__(*args, **kw)

    def __getattr__(self, name):
        return getattr(self.__wrapped__, name)


def track(fn: Callable, label: str,
          donate_argnums: tuple[int, ...] | None = None) -> Callable:
    """Wrap a jitted callable so each invocation counts one dispatch
    round trip under ``label``.  The wrapper adds one Python ``if`` per
    call and never touches the arguments — the traced program and its
    dispatch count are identical with telemetry on or off.

    With telemetry on, the program's first call also reads its op map
    into ``telemetry.scopes`` (instruction of the optimized HLO → the
    ``op_name`` that holds the program's ``jax.named_scope``s): from its
    text where ``fn`` is an AOT ``Compiled``, else from a lowering for
    that call's arguments; once per tracked object.

    ``donate_argnums`` (PR 19) declares the callable's donation
    signature to the memory ledger: at each call memrec claims the
    newest live buffers matching the donated args' byte sizes and
    records them leaving the live set (the runtime twin of HL303) —
    metadata only, the args are never materialized."""
    if donate_argnums is not None:
        memrec.register_dispatch(label, donate_argnums)
    return _Tracked(fn, label)


# ---------------------------------------------------------------------------
# Budget guard
# ---------------------------------------------------------------------------

class BudgetExceeded(RuntimeError):
    """A flight-recorder budget was violated (see :func:`budget`)."""


_BUDGET_KEYS = ("compiles", "compile_s", "h2d_bytes", "h2d_calls",
                "dispatches", "readbacks", "d2h_bytes")


def snapshot() -> dict:
    """Current cumulative counters (the budget guard's baseline)."""
    return {"compiles": compile_watch.count,
            "compile_s": round(compile_watch.total_s, 6),
            "h2d_bytes": transfers.h2d_bytes,
            "h2d_calls": transfers.h2d_calls,
            "dispatches": transfers.dispatches,
            "readbacks": transfers.readbacks,
            "d2h_bytes": transfers.d2h_bytes}


def delta_since(base: dict) -> dict:
    now = snapshot()
    return {k: (round(now[k] - base[k], 6) if k == "compile_s"
                else now[k] - base[k]) for k in _BUDGET_KEYS}


class _BudgetScope:
    """Yielded by :func:`budget`: ``spent()`` reads the live deltas."""

    def __init__(self, base: dict):
        self._base = base

    def spent(self) -> dict:
        return delta_since(self._base)


def _notify_health(tag: str, over: list[tuple[str, Any, Any]]) -> None:
    """WARN-mode violations also land on the health monitor's
    budget-drift detector (PR 14) — a trap that fires mid-bench leaves
    committed evidence instead of a scrolled RuntimeWarning.  Raise-mode
    violations are already loud (they kill the test); only warn mode
    needs the paper trail."""
    from harp_tpu import health

    health.monitor.observe_budget(tag, over)


@contextlib.contextmanager
def budget(compiles: int | None = None, h2d_bytes: int | None = None,
           dispatches: int | None = None, readbacks: int | None = None,
           d2h_bytes: int | None = None, h2d_calls: int | None = None,
           *, action: str = "raise", tag: str = ""):
    """Enforce execution-discipline bounds over a block.

    Each keyword is an inclusive upper bound on that counter's *delta*
    across the block (None = unbounded).  On violation: ``action="raise"``
    raises :class:`BudgetExceeded` naming every exceeded counter (the
    tests' mode); ``action="warn"`` emits a ``RuntimeWarning`` and
    continues (the bench mode — a measurement run must record the
    number, not die).  The CLAUDE.md driver-loop traps map one-to-one:

    - ``compiles=N``: a silent re-trace (e.g. ``PRNGKey(python_int)``
      baked into a per-step jit) blows the compile count;
    - ``readbacks=1``: per-epoch readback loops instead of one stacked
      readback per run;
    - ``h2d_bytes=B``: re-uploading device-resident data;
    - ``dispatches=N``: per-epoch dispatch instead of one scanned program.

    No-op (yields without snapshotting) when telemetry is disabled —
    enable with ``HARP_TELEMETRY=1`` or ``telemetry.scope()`` first, or
    the guard guards nothing.  If the block raises, the original
    exception propagates unchecked.
    """
    if not telemetry.enabled():
        yield None
        return
    limits = {"compiles": compiles, "h2d_bytes": h2d_bytes,
              "h2d_calls": h2d_calls, "dispatches": dispatches,
              "readbacks": readbacks, "d2h_bytes": d2h_bytes}
    scope_ = _BudgetScope(snapshot())
    yield scope_
    spent = scope_.spent()
    over = [(name, spent[name], limit)
            for name, limit in limits.items()
            if limit is not None and spent[name] > limit]
    if over:
        msg = (f"flight-recorder budget exceeded"
               f"{f' [{tag}]' if tag else ''}: "
               + "; ".join(f"{n} used {s} > budget {l}"
                           for n, s, l in over))
        if action == "warn":
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
            _notify_health(tag or _call_site(), over)
        else:
            raise BudgetExceeded(msg)


class SteadyState:
    """Per-batch budget for long-lived loops (the ``harp serve`` guard).

    :func:`budget` guards one block; a serving loop needs the same bound
    re-applied to every batch forever, plus an account of how the steady
    state actually spent — so the bench row can *prove* "0 compiles in
    steady state" rather than assert it.  Usage::

        steady = flightrec.SteadyState(compiles=0, dispatches=1,
                                       readbacks=1, tag="serve.kmeans")
        for batch in batches:
            with steady.batch():
                out = exe(*state, x)        # 1 tracked dispatch
                res = flightrec.readback(out)  # 1 stacked readback
        steady.summary()  # {"batches", "violations", + counter deltas}

    ``action="raise"`` (default) raises :class:`BudgetExceeded` on the
    offending batch (tests); ``action="warn"`` warns and keeps serving,
    counting the violation (production — a server must not die because
    one batch recompiled, but the row must say it happened).  Like
    :func:`budget`, a batch is a no-op while telemetry is disabled.
    """

    def __init__(self, compiles: int | None = 0,
                 dispatches: int | None = 1, readbacks: int | None = 1,
                 h2d_bytes: int | None = None,
                 d2h_bytes: int | None = None,
                 h2d_calls: int | None = None, *,
                 action: str = "raise", tag: str = "steady"):
        self.limits = {"compiles": compiles, "dispatches": dispatches,
                       "readbacks": readbacks, "h2d_bytes": h2d_bytes,
                       "d2h_bytes": d2h_bytes, "h2d_calls": h2d_calls}
        self.action = action
        self.tag = tag
        self.reset()

    def reset(self) -> None:
        """Start a fresh steady-state window (server startup calls this
        so startup compiles never count against the steady summary)."""
        self.batches = 0
        self.violations = 0
        self._base = snapshot() if telemetry.enabled() else None

    @contextlib.contextmanager
    def batch(self):
        if not telemetry.enabled():
            yield None
            return
        if self._base is None:  # telemetry enabled after construction
            self._base = snapshot()
        base = snapshot()
        yield None
        spent = delta_since(base)
        self.batches += 1
        over = [(k, spent[k], v) for k, v in self.limits.items()
                if v is not None and spent[k] > v]
        if over:
            self.violations += 1
            msg = (f"steady-state budget exceeded [{self.tag}] batch "
                   f"{self.batches}: "
                   + "; ".join(f"{k} used {s} > budget {v}"
                               for k, s, v in over))
            if self.action == "warn":
                warnings.warn(msg, RuntimeWarning, stacklevel=3)
                _notify_health(self.tag, over)
            else:
                raise BudgetExceeded(msg)

    def summary(self) -> dict:
        """Batch/violation counts + cumulative counter deltas since
        :meth:`reset` (deltas absent when telemetry never enabled)."""
        out = {"batches": self.batches, "violations": self.violations}
        if self._base is not None:
            out.update(delta_since(self._base))
        return out

    def verify_exact(self, batches: int, *, compiles: int = 0) -> dict:
        """Overlap-mode exact accounting: the continuous serving loop
        dispatches batch t+1 before it reads batch t back, so one
        :meth:`batch` window no longer pairs a dispatch with ITS
        readback — the per-window budget still bounds each window, but
        only the totals can prove the pipeline stayed exact.  Asserts
        that since :meth:`reset` the loop spent EXACTLY one dispatch
        and one readback per dispatched batch and exactly ``compiles``
        compiles: over-spending is the classic trap, and UNDER-spending
        means work bypassed the tracked executables (equally wrong —
        an untracked dispatch is invisible to every budget).  Returns
        the spent dict; raises/warns per the instance ``action``.
        No-op ({} returned) when telemetry never enabled.
        """
        if self._base is None:
            return {}
        spent = delta_since(self._base)
        wrong = [(k, spent[k], want)
                 for k, want in (("compiles", compiles),
                                 ("dispatches", batches),
                                 ("readbacks", batches))
                 if spent[k] != want]
        if wrong:
            self.violations += 1
            msg = (f"steady-state exact accounting failed [{self.tag}] "
                   f"over {batches} batches: "
                   + "; ".join(f"{k} spent {s} != exactly {w}"
                               for k, s, w in wrong))
            if self.action == "warn":
                warnings.warn(msg, RuntimeWarning, stacklevel=2)
                _notify_health(self.tag, wrong)
            else:
                raise BudgetExceeded(msg)
        return spent


# ---------------------------------------------------------------------------
# Per-operation overheads of the graded rows (the perfmodel readout)
# ---------------------------------------------------------------------------

#: Fixed per-operation costs of the execution plane AS THEY WERE when the
#: committed BENCH_local rows were measured (1× v5e, 2026-07-30 …
#: 08-01).  The offline cost model (:mod:`harp_tpu.perfmodel`) grades
#: itself against those rows, so it prices its ``overhead`` term with
#: the costs those rows paid.  NOT MEASURED ON THE CURRENT HOST: that
#: machine reached its chip over a slower link than this one does
#: (chip_smoke.py stages 300 MB in well under a second here), so do not
#: read these as facts about today's dispatch, compile or staging cost —
#: re-calibration against fresh chip rows is ROADMAP Design 6.
#:
#: - ``dispatch_s`` / ``readback_s``: one driver→device dispatch / one
#:   blocking D2H fetch (the budget(dispatches=1, readbacks=1)
#:   discipline makes a run pay each exactly once);
#: - ``compile_s``: one small XLA backend compile (the
#:   PRNGKey-specialization recompile, HL002);
#: - ``h2d_gbs``: the host→device staging rate.
GRADED_ROW_OVERHEADS = {
    "dispatch_s": 0.020,
    "readback_s": 0.020,
    "compile_s": 0.140,
    "h2d_gbs": 0.030e9,
}


# ---------------------------------------------------------------------------
# Export
# ---------------------------------------------------------------------------

def provenance_stamp() -> dict:
    """backend/date/commit triple for exported rows — compile/transfer
    rows are *evidence about a specific backend* (a CPU-sim compile count
    must never read as chip evidence), so unlike comm/span rows
    they carry the same stamp scripts/check_jsonl.py demands of bench
    rows (invariant 4)."""
    from harp_tpu.utils.metrics import _provenance

    prov = _provenance()
    return {k: prov.get(k) for k in _PROV_FIELDS}


def export_jsonl(fh) -> None:
    """Append compile + transfer rows (telemetry.export calls this)."""
    if not compile_watch.records and not transfers._sites:
        return
    stamp = provenance_stamp()
    compile_watch.export_jsonl(fh, stamp)
    transfers.export_jsonl(fh, stamp)
