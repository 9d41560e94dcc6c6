"""The window protocol: set-up, warm-up, a measured window of whole
blocks or jobs, the correctness check, the result.  Driven entirely by
the files :mod:`perf.spec` finds; holds no cell, configuration or metric
name.

    set-up     everything from process start to the first measured block:
               data from the seed, staging, compiles or cache loads, one
               warm-up block of the very shape the window runs
    window     blocks (``mode: steady``) or jobs (``mode: job``) one after
               another, each ending in a readback, until ``--seconds`` have
               passed; the window ends with the block that crosses the line
    check      outside the window, against ``perf/reference``
    trace      with ``--trace 1`` the profiler runs over a few whole blocks
               inside the window (``trace_seconds`` of the traffic file)

Counters come from the program's own hooks (``flightrec`` observers), host
spans from :class:`Recorder`, which also writes each span into the
profiler's trace as ``perf:<name>`` so that device gaps can be labelled.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import statistics
import sys
import time

from perf import spec, trace_reduce, workmodels

TRACE_DIR = ".perf_trace"


class Recorder:
    """Host spans and counters of one run."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []
        self.counts = {"dispatches": 0, "readbacks": 0, "h2d_bytes": 0,
                       "h2d_calls": 0, "compile_events": 0, "cache_hits": 0,
                       "compile_s": 0.0}
        self._open_span = None
        self._advance: dict[str, str] = {}

    # -- spans ----------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def _open(self, name: str) -> None:
        import jax

        ann = jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)
        ann.__enter__()
        self._open_span = (name, time.perf_counter(), ann)

    def _close(self) -> None:
        name, t0, ann = self._open_span
        ann.__exit__(None, None, None)
        self.spans.append((name, t0, time.perf_counter()))
        self._open_span = None

    @contextlib.contextmanager
    def phases(self, first: str, advance: dict[str, str]):
        """Split a call into the program by what the program does inside
        it: the span ``first`` runs until the first event of a kind in
        ``advance`` (``h2d``, ``dispatch``, ``readback``), which closes
        it and opens the span named there; each kind advances once."""
        self._advance = dict(advance)
        self._open(first)
        try:
            yield
        finally:
            self._close()
            self._advance = {}

    def _event(self, kind: str) -> None:
        nxt = self._advance.pop(kind, None)
        if nxt is not None:
            self._close()
            self._open(nxt)

    def durations(self, name: str, t0: float = -math.inf,
                  t1: float = math.inf) -> list[float]:
        return [e - s for n, s, e in self.spans
                if n == name and s >= t0 and e <= t1]

    # -- counters -------------------------------------------------------
    def on_dispatch(self, label: str) -> None:
        self.counts["dispatches"] += 1
        self._event("dispatch")

    def on_readback(self, x) -> None:
        self.counts["readbacks"] += 1
        self._event("readback")

    def on_h2d(self, nbytes: int, site) -> None:
        self.counts["h2d_bytes"] += int(nbytes)
        self.counts["h2d_calls"] += 1
        self._event("h2d")

    def on_compile(self, kind: str, seconds: float) -> None:
        # a persistent-cache hit fires both events: the load's seconds as
        # a compile, and the hit
        if kind == "cache_hit":
            self.counts["cache_hits"] += 1
        else:
            self.counts["compile_events"] += 1
            self.counts["compile_s"] += float(seconds)

    @contextlib.contextmanager
    def watching(self):
        from harp_tpu.utils import flightrec

        with flightrec.observe_dispatches(self.on_dispatch), \
                flightrec.observe_readbacks(self.on_readback), \
                flightrec.observe_h2d(self.on_h2d), \
                flightrec.observe_compiles(self.on_compile):
            yield self

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, base: dict) -> dict:
        return {k: self.counts[k] - base[k] for k in self.counts}


class RunData:
    """What a metric reader is handed: plain attributes, filled in as the
    run goes.  ``trace`` is :func:`trace_reduce.reduce`'s dict, or None in
    an untraced run."""

    def __init__(self, cell: spec.Cell, rec: Recorder):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.rec = rec
        self.device_kind = None
        self.setup_s = None
        self.window = (0.0, 0.0)      # host clock, start and end
        self.block_s: list[float] = []
        self.items = 0                # items completed in the window
        self.in_window: dict = {}     # counters inside the window
        self.in_setup: dict = {}      # counters before it
        self.trace = None
        self.trace_items = 0          # items completed in the traced blocks
        self.trace_blocks = 0
        self.least = None             # workmodels.least_seconds, traced run
        self.comm_bytes = None        # CommLedger bytes inside the window
        self.extra: dict = {}         # what the driver adds (counts only)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _place_compile_cache(root: str) -> str:
    """Before the first compile: the persistent cache at the given place
    or at ``<checkout>/.jax_cache``, and every compile kept, however
    short (JAX's own thresholds skip those under a second, and the main
    path has ~120 of them)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
            # a process that already compiled elsewhere (the tests run
            # several checkouts in one) must open the new directory
            from jax.experimental.compilation_cache import compilation_cache

            compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _devices(chips: int, require_platform: str | None):
    import jax

    devs = jax.devices()
    if require_platform is not None and devs[0].platform != require_platform:
        raise SystemExit(
            f"perf: needs a {require_platform.upper()}, found platform="
            f"{devs[0].platform!r} ({devs[0].device_kind}, {len(devs)} "
            "device(s)); nothing falls back")
    if len(devs) < chips:
        raise SystemExit(f"perf: the cell asks for {chips} chip(s), JAX "
                         f"found {len(devs)}")
    return devs


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _percentile_line(samples: list[float]) -> dict:
    """Median, count, and the highest percentile with at least ten
    samples beyond it (none under twenty samples)."""
    s = sorted(samples)
    out = {"n": len(s), "median_s": statistics.median(s) if s else None}
    if len(s) >= 20:
        idx = len(s) - 11  # ten samples lie beyond this one
        out["p"] = round(100.0 * (idx + 1) / len(s), 1)
        out["p_value_s"] = s[idx]
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, start_clock: float | None = None,
             require_platform: str | None = "tpu",
             override: dict | None = None, say=print) -> dict:
    """Run one cell once; returns the object of the last line.

    ``override`` (``{"data": {...}, "traffic": {...}, ...}``) replaces
    keys of the traffic file and of the configuration's blocks, and is
    for rehearsals at a tiny shape in the tests only; ``run.py`` never
    passes it, nor anything but ``require_platform="tpu"``.
    """
    start_clock = time.perf_counter() if start_clock is None else start_clock
    cell = spec.Cell(root, workload)
    for block, keys in (override or {}).items():
        if block == "traffic":
            cell.traffic = {**cell.traffic, **keys}
        else:
            cell.config = {**cell.config,
                           block: {**cell.config[block], **keys}}
    cache_dir = _place_compile_cache(root)
    all_devices = _devices(cell.chips, require_platform)
    devices = all_devices[:cell.chips]
    on_chip = devices[0].platform == "tpu"

    from harp_tpu.utils import telemetry

    rec = Recorder()
    run = RunData(cell, rec)
    run.device_kind = devices[0].device_kind
    # the program's own ledgers (collective bytes, partition padding) only
    # count with its telemetry on; the timed, untraced run leaves it off
    telemetry_was = telemetry.enabled()
    telemetry.enable(bool(trace))
    steady = cell.traffic["mode"] == "steady"

    with rec.watching():
        driver = cell.driver_module().Driver(
            cell.config, cell.traffic, devices, seed, rec)
        with rec.span("setup"):
            driver.setup()
        one = driver.block if steady else driver.job
        with rec.span("warmup"):
            _, warm_ok = one()
        run.in_setup = rec.snapshot()
        comm0 = telemetry.ledger.volume() if trace else None

        # ---- the window ------------------------------------------------
        import jax

        attempted = failed = 0

        def timed_block(traced: bool = False) -> None:
            nonlocal attempted, failed
            tb = time.perf_counter()
            items, ok = one()
            run.block_s.append(time.perf_counter() - tb)
            attempted += 1
            failed += 0 if ok else 1
            run.items += items
            if traced:
                run.trace_items += items
                run.trace_blocks += 1

        trace_dir = os.path.join(root, TRACE_DIR, workload)
        base = rec.snapshot()
        t0 = time.perf_counter()
        run.setup_s = t0 - start_clock
        if trace:
            timed_block()
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            trace_for = float(cell.traffic.get("trace_seconds", 3.0))
            with rec.span("window"):
                t_trace = time.perf_counter()
                while True:
                    timed_block(traced=True)
                    if time.perf_counter() - t_trace >= trace_for:
                        break
            jax.profiler.stop_trace()
        while time.perf_counter() - t0 < seconds:
            timed_block()
        now = time.perf_counter()
        run.window = (t0, now)
        run.in_window = rec.since(base)
        if trace:
            run.comm_bytes = telemetry.ledger.volume() - comm0
        peak = _peak_bytes(devices)

        # ---- outside the window ------------------------------------------
        with rec.span("check"):
            verdict = driver.check()
        run.extra = driver.extra()
    telemetry.enable(telemetry_was)

    correct = bool(verdict["correct"]) and warm_ok and failed == 0
    # every number the check held, beside its limit (plain numbers)
    compared = json.loads(spec.dumps(
        {k: {"value": v, "limit": verdict[k + "_limit"]}
         for k, v in verdict.items() if k + "_limit" in verdict}))
    say("info " + spec.dumps({
        "cell": workload, "seed": seed, "mode": cell.traffic["mode"],
        "item": cell.config["item"], "items": run.items,
        "blocks": attempted, "window_s": run.window_s,
        "block_s": _percentile_line(run.block_s),
        "setup": {k: (round(v, 3) if isinstance(v, float) else v)
                  for k, v in run.in_setup.items()},
        "setup_spans_s": {n: round(e - s, 3) for n, s, e in rec.spans
                          if e <= t0 and n != "setup"},
        "in_window": run.in_window, "cache_dir": cache_dir,
        "check_s": round(rec.durations("check")[0], 3),
        "check": verdict}))

    device = {"platform": devices[0].platform, "kind": run.device_kind,
              "count": len(all_devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        pd = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        run.trace = trace_reduce.reduce(pd)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
        if on_chip and "work" in cell.config:
            run.least = workmodels.least_seconds(
                cell.config["work"], run.trace_items / cell.chips,
                run.device_kind)
        say("info " + spec.dumps({
            "trace": {k: run.trace[k] for k in
                      ("window_s", "busy_s", "class_s", "n_devices")},
            "trace_blocks": run.trace_blocks,
            "trace_items": run.trace_items,
            "least_seconds_per_chip": run.least}))
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(group):
        value = cell.reader(group, m["name"])(run)
        if value is None:
            continue  # nothing to read: the metric is left out
        if not on_chip and m["source"] != "program_counter":
            # a CPU rehearsal has no time, rate or share to report
            metrics[m["name"]] = {"value": None, "unit": m["unit"],
                                  "note": "not measured: no chip"}
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    out["compared"] = compared  # last on the line
    for name, c in compared.items():  # and standard error's last lines
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return out
