#!/usr/bin/env python
"""Benchmark entry point — prints ONE JSON line for the driver.

Covers the north-star pair (SURVEY.md §1: KMeans iter/s + MF-SGD
updates/s/chip) and the other graded configs (LDA, MLP, subgraph, RF) in
a single record: the headline metric/value/unit/vs_baseline fields are
KMeans on graded config #1 (k=100, 1M×300 dense), and ``submetrics``
carries one entry per additional config.  The record names the device
it ran on (``platform`` / ``device_kind`` / ``n_devices``).

Full mode measures, so it runs on a TPU or not at all: the first thing
it does is :func:`harp_tpu.utils.chip.require_tpu`, which exits non-zero
on any other backend (JAX's own silent drop to the CPU included).
``--smoke`` is the CPU-safe correctness pass over the same twelve cells
at toy shapes; its numbers are not speeds.  One process per chip: this
script starts no child.

A cell that raises or times out leaves ``value: null`` and an ``error``
in its entry, the other cells still run, and the process then exits 1 —
a number that was not measured is never printed.

``vs_baseline`` compares against the v0 numbers in BASELINE.md (1× TPU
v5e, 2026-07-29 … 08-01) — a regression guard vs our own best, not a
reference claim (no published Harp figure is pinned; BASELINE.json
``published`` is empty).

Timing notes (see harp_tpu/utils/timing.py): all iterations run inside
one jitted program and sync is a scalar readback.  A whole-run watchdog
(``HARP_BENCH_TIMEOUT``, default 1200 s, re-armed per config) turns a
hang into a record with every config measured so far and exit code 3;
``--max-seconds-per-config=SECONDS`` (PR 10) adds a bounded per-config
timer under it: the config runs on a worker thread, and on overrun the
sweep records the timeout in that config's entry, abandons the thread,
and keeps measuring.
"""

import json
import os
import sys
import threading
import traceback

sys.path.insert(0, __file__.rsplit("/", 1)[0])

# reusable benchmark artifacts (shared with scripts/measure_all.py) —
# absolute, so the driver can invoke bench.py from any cwd
_BENCH_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           ".bench_data")

# Regression baselines, 1× TPU v5e (BASELINE.md) — re-measured on
# ROUND-5 code 2026-08-01, the day every candidate was measured and the
# winners flipped (FLIP_DECISIONS.jsonl): MFSGDConfig.algo and
# LDAConfig.algo/sampler/rng_impl/carry_db now default to the measured
# winners; the dense arms remain pinned configs for regression tracking.
# None = no TPU number recorded yet (vs_baseline stays null until one is).
BASELINES = {
    "kmeans": 381.2,        # iter/s, 1M×300 k=100 f32 (±5% window spread)
    "kmeans_int8_fused": 555.1,  # fused int8 kernel — the int8-path
                            # default since the 2026-08-01 flip (1.14×
                            # XLA int8 at equal inertia, 8000-row tiles)
    "kmeans_stream": 0.53,  # iter/s end-to-end, 100M×300 k=1000 (1.09 ex-gen)
    "kmeans_ingest": None,  # points/s, 20M×300 f16 disk npy: the
                            # 2026-08-01 row was bound by that day's
                            # host link, not by this system — no
                            # baseline until it is measured here
    "mfsgd": 83.1e6,        # updates/s/chip, ML-20M shapes, dense algo
    "mfsgd_pallas": 246.5e6,  # fused kernel — the DEFAULT algo since the
                            # 2026-08-01 flip; 256×256 auto-tile after
                            # the same-day sweep (250.2M vs 195.5M at
                            # 512; 246.5M re-confirmed through the
                            # default path) = 2.97× dense, equal RMSE
    "lda": 6.46e6,          # tokens/s/chip, 100k docs × 1k topics, dense
    "lda_pallas": 7.92e6,   # fused kernel, carry pinned off (incumbent arm)
    "lda_pallas_carry": 10.50e6,  # kernel + Db-carry — the DEFAULT
                            # LDAConfig stack since the 2026-08-01 flip
                            # (1.63× dense at equal likelihood)
    "mlp": 22.1e6,          # samples/s, MNIST shapes, device-resident
    "subgraph": 75.8e3,     # vertices/s, u5-tree on 100k vertices —
                            # post-compaction: the compact tables win
                            # +10% at the graded 1M shape (129.2k) but
                            # cost ~19% at this small uniform shape
    "rf": 8.80,             # trees/s, 32 trees depth 6 on 200k×64
}

# result_key → display unit
UNITS = {
    "iters_per_sec": "iter/s",
    "points_per_sec": "points/s",
    "updates_per_sec_per_chip": "updates/s/chip",
    "tokens_per_sec_per_chip": "tokens/s/chip",
    "samples_per_sec": "samples/s",
    "vertices_per_sec": "vertices/s",
    "trees_per_sec": "trees/s",
}


def _flip_state():
    """Summary of FLIP_DECISIONS.jsonl for the driver record: how much of
    the candidates table has real verdicts, and how many flips the gate
    has authorized.  None before the gate has ever produced the file."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "FLIP_DECISIONS.jsonl")
    rows = []
    try:
        with open(path) as f:
            for ln in f:
                if not ln.strip():
                    continue
                try:
                    row = json.loads(ln)
                except ValueError:
                    continue  # truncated tee line (sprint killed mid-write)
                if "flip_decision" in row:
                    rows.append(row)
    except OSError:
        return None
    if not rows:
        return None
    return {"candidates": len(rows),
            "decided": sum(1 for r in rows
                           if r.get("speedup") is not None
                           and r.get("quality_ok") is not None),
            "flips_authorized": sum(1 for r in rows if r.get("flip"))}


def _ingest_bench(smoke):
    """Real disk ingest through fit_streaming (VERDICT r2 item 2): full
    mode streams a reusable 20M×300 f16 npy from .bench_data/ — the
    first run pays a ~4 min generation, later runs reuse the file.
    Presets live in scripts/bench_ingest.py (run_smoke/run_full) so this
    and measure_all can never drift apart."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import bench_ingest

    return bench_ingest.run_smoke() if smoke else bench_ingest.run_full()


# config name → result_key, in run order (headline first).
# kmeans_ingest runs LAST: full mode can pay ~864 s of file generation,
# and an overrun there must cost only itself, not the configs after it
# (same rule as measure_all).
_CONFIG_KEYS = [
    ("kmeans", "iters_per_sec"),
    ("kmeans_int8_fused", "iters_per_sec"),
    ("kmeans_stream", "iters_per_sec"),
    ("mfsgd", "updates_per_sec_per_chip"),
    ("mfsgd_pallas", "updates_per_sec_per_chip"),
    ("lda", "tokens_per_sec_per_chip"),
    ("lda_pallas", "tokens_per_sec_per_chip"),
    ("lda_pallas_carry", "tokens_per_sec_per_chip"),
    ("mlp", "samples_per_sec"),
    ("subgraph", "vertices_per_sec"),
    ("rf", "trees_per_sec"),
    ("kmeans_ingest", "points_per_sec"),
]


def _configs(smoke):
    """(name, unit, result_key, thunk) per graded config, headline first."""
    from harp_tpu.models import (kmeans, kmeans_stream, lda, mfsgd, mlp, rf,
                                 subgraph)

    import jax

    thunks = {
        "kmeans": lambda: kmeans.benchmark(
            # use_pallas=False pins the f32 XLA arm (the f32 auto is
            # also False today, but the row identity must not follow a
            # future default change)
            use_pallas=False,
            **({"n": 8192, "d": 32, "k": 16, "iters": 20, "warmup": 2}
               if smoke else
               {"n": 1_000_000, "d": 300, "k": 100, "iters": 100,
                "warmup": 5})),
        # the int8-path default since the 2026-08-01 flip, knobs pinned
        "kmeans_int8_fused": lambda: kmeans.benchmark(
            quantize="int8", use_pallas=True,
            **({"n": 8192, "d": 32, "k": 16, "iters": 20, "warmup": 2}
               if smoke else
               {"n": 1_000_000, "d": 300, "k": 100, "iters": 100,
                "warmup": 5})),
        "kmeans_stream": lambda: kmeans_stream.benchmark_streaming(
            **({"n": 65536, "d": 16, "k": 16, "iters": 2,
                "chunk_points": 8192} if smoke else
               {"n": 100_000_000, "d": 300, "k": 1000, "iters": 2,
                "chunk_points": 262_144})),
        "kmeans_ingest": lambda: _ingest_bench(smoke),
        "mfsgd": lambda: mfsgd.benchmark(
            **({"n_users": 512, "n_items": 256, "nnz": 20_000, "rank": 8,
                "epochs": 2, "u_tile": 16, "i_tile": 16, "entry_cap": 256}
               if smoke else {})),
        "mfsgd_pallas": lambda: mfsgd.benchmark(
            algo="pallas",
            # smoke tiles must pass the kernel's TPU gate (128-multiples)
            **({"n_users": 512, "n_items": 256, "nnz": 20_000, "rank": 8,
                "epochs": 2, "u_tile": 128, "i_tile": 128,
                "entry_cap": 256} if smoke else {})),
        "lda": lambda: lda.benchmark(
            **({"n_docs": 256, "vocab_size": 128, "n_topics": 8,
                "tokens_per_doc": 16, "epochs": 1, "d_tile": 16,
                "w_tile": 16, "entry_cap": 64} if smoke else
               # pack cache shared with measure_all: full-shape host
               # packing (~31 s) is paid once per tiling, not per run
               {"pack_cache": _BENCH_DATA})),
        "lda_pallas": lambda: lda.benchmark(
            algo="pallas",
            # smoke tiles must pass the kernel's TPU gate (128-multiples)
            **({"n_docs": 256, "vocab_size": 128, "n_topics": 8,
                "tokens_per_doc": 16, "epochs": 1, "d_tile": 128,
                "w_tile": 128, "entry_cap": 64} if smoke else
               {"pack_cache": _BENCH_DATA})),
        # the DEFAULT LDAConfig stack since the 2026-08-01 flip (the
        # benchmark entry pins every knob explicitly so this row's
        # identity survives any future default change).  Since PR 32 the
        # doc-tile carry is the kernel's own, so this and `lda_pallas`
        # run one program; both names stay for the evidence rows
        "lda_pallas_carry": lambda: lda.benchmark(
            algo="pallas", carry_db=True,
            **({"n_docs": 256, "vocab_size": 128, "n_topics": 8,
                "tokens_per_doc": 16, "epochs": 1, "d_tile": 128,
                "w_tile": 128, "entry_cap": 64} if smoke else
               {"pack_cache": _BENCH_DATA})),
        "mlp": lambda: mlp.benchmark(
            **({"n": 4096, "batch": 512, "steps": 5} if smoke else {})),
        "subgraph": lambda: subgraph.benchmark(
            **({"n_vertices": 2000, "avg_degree": 4} if smoke else {})),
        "rf": lambda: rf.benchmark(
            **({"n": 4096, "f": 16, "max_depth": 3,
                "n_trees": 2 * jax.device_count()} if smoke else {})),
    }
    return [(name, UNITS[key], key, thunks[name])
            for name, key in _CONFIG_KEYS]


def _parse_max_seconds(argv):
    """``--max-seconds-per-config=SECONDS`` (the ``=`` form only: a bare
    following token would be swallowed by the positional config filter).
    None when absent; SystemExit on a malformed value."""
    for a in argv:
        if a.startswith("--max-seconds-per-config"):
            if "=" not in a:
                print("bench.py: use --max-seconds-per-config=SECONDS "
                      "(the '=' form)", file=sys.stderr)
                raise SystemExit(2)
            try:
                v = float(a.split("=", 1)[1])
            except ValueError:
                print(f"bench.py: bad --max-seconds-per-config value "
                      f"{a.split('=', 1)[1]!r}", file=sys.stderr)
                raise SystemExit(2)
            if v <= 0:
                print("bench.py: --max-seconds-per-config must be > 0",
                      file=sys.stderr)
                raise SystemExit(2)
            return v
    return None


def _run_with_timeout(thunk, max_s):
    """Per-config watchdog (subprocess-free): run ``thunk`` on a daemon
    worker thread and wait at most ``max_s`` seconds.  On timeout the
    thread is ABANDONED (a hung device call is uninterruptible from
    Python) and ``(None, error_string)`` returns so the sweep
    moves on: one hung config costs its own budget, not the rest of the
    measurement window.  Exceptions from the thunk re-raise in the
    caller (the existing per-config error handling owns them)."""
    if max_s is None:
        return thunk(), None
    box = {}

    def run():
        try:
            box["res"] = thunk()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            box["exc"] = e

    t = threading.Thread(target=run, daemon=True,
                         name="bench-config-worker")
    t.start()
    t.join(max_s)
    if t.is_alive():
        return None, (f"timeout: config exceeded "
                      f"--max-seconds-per-config={max_s:g}s; skipped "
                      "(worker thread abandoned)")
    if "exc" in box:
        raise box["exc"]
    return box["res"], None


def main() -> int:
    from harp_tpu.utils import chip
    from harp_tpu.utils.timing import HangWatchdog

    smoke = "--smoke" in sys.argv
    max_seconds = _parse_max_seconds(sys.argv[1:])
    only = [a for a in sys.argv[1:] if not a.startswith("-")]
    unknown = set(only) - set(BASELINES)
    if unknown:
        # typo → loud error, not a clean-looking all-null record
        print(f"bench.py: unknown config(s) {sorted(unknown)}; "
              f"choose from {sorted(BASELINES)}", file=sys.stderr)
        raise SystemExit(2)
    chip.setup_compile_cache()
    # full mode measures: a TPU or nothing.  --smoke names its device too
    device = chip.device_info() if smoke else chip.require_tpu("bench.py")
    done = threading.Event()  # set once the result line is out
    sub: dict = {}            # filled as configs complete (thread-shared)
    suffix = "_smoke" if smoke else ""

    kmeans_selected = not only or "kmeans" in only

    def record(error=None):
        km = sub.get("kmeans", {})
        rec = {
            "metric": ("kmeans_iters_per_sec" + suffix if smoke
                       else "kmeans_iters_per_sec_1Mx300_k100"),
            # null, never 0.0, for a headline that was filtered out or
            # failed: a number that was not measured is not printed
            "value": km.get("value"),
            "unit": "iter/s",
            "vs_baseline": (km.get("vs_baseline") if not smoke else None),
            **device,
            "submetrics": {k: v for k, v in sub.items() if k != "kmeans"},
        }
        for k in ("achieved_tflops", "achieved_gbs", "pct_peak_flops",
                  "pct_peak_bw", "bound"):  # headline roofline context
            if k in km:
                rec[k] = km[k]
        if not kmeans_selected:
            rec["headline_skipped"] = True
        fs = _flip_state()
        if fs is not None:
            # protocol state travels with the record: the judge/driver can
            # see how much of the candidates table has verdicts without
            # opening FLIP_DECISIONS.jsonl
            rec["flip_state"] = fs
        # a kmeans exception must surface on the headline, not vanish
        # when submetrics drops the kmeans key
        error = error or km.get("error")
        if error:
            rec["error"] = error
        return rec

    def emit_hang_record(what):
        # the driver expects ONE JSON line; a hang should still produce a
        # parseable record (with every config measured so far) rather than
        # silence + exit code 3 — but never a SECOND line if the timer
        # fires in the completion/cancel window
        if done.is_set():
            return
        done.set()
        print(json.dumps(record(
            error=f"no result from {what} (watchdog)")), flush=True)

    # flight recorder (HARP_TELEMETRY=1): each config gets a span plus a
    # per-config delta of the execution counters in its submetric — a
    # silent recompile or an extra readback inside a measured config is
    # visible in the driver record, not re-derived from wall-clock.
    # The memory ledger (PR 19) rides the same pattern: per-config peak
    # HBM + headroom beside the flight delta.
    from harp_tpu.utils import flightrec, memrec, telemetry
    from harp_tpu.utils.roofline import annotate

    failed = False
    watchdog = HangWatchdog(on_fire=emit_hang_record)  # HARP_BENCH_TIMEOUT
    watchdog.arm("backend init")
    for name, unit, key, thunk in _configs(smoke):
        if only and name not in only:
            continue
        watchdog.arm(f"bench.py {name}")
        flight_base = flightrec.snapshot() if telemetry.enabled() else None
        mem_base = memrec.snapshot() if telemetry.enabled() else None
        try:
            with telemetry.span(f"bench.{name}"):
                res, err = _run_with_timeout(thunk, max_seconds)
        except Exception as e:  # noqa: BLE001 - boundary: the other cells
            traceback.print_exc()  # still run; the process then exits 1
            res, err = None, f"{type(e).__name__}: {e}"
        if err is not None:
            print(f"bench.py: {name} FAILED: {err}", file=sys.stderr,
                  flush=True)
            sub[name] = {"value": None, "unit": unit, "error": err}
            failed = True
            continue
        value = float(res[key])
        base = BASELINES[name]
        # roofline context travels with the driver record, so a measured
        # rate reads as %-of-datasheet-peak of the device it ran on
        ann = annotate(name, res, device["device_kind"])
        roof = {k: ann[k] for k in ("achieved_tflops", "achieved_gbs",
                                    "pct_peak_flops", "pct_peak_bw",
                                    "bound") if k in ann and k not in res}
        sub[name] = {"value": round(value, 2), "unit": unit,
                     "vs_baseline": (None if smoke or base is None else
                                     round(value / base, 4)), **roof}
        if flight_base is not None:
            sub[name]["flight"] = flightrec.delta_since(flight_base)
        if mem_base is not None:
            sub[name]["memory"] = memrec.delta_since(mem_base)
    watchdog.cancel()
    done.set()
    print(json.dumps(record()), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
