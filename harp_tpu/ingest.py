"""Prefetch-pipelined host→device ingest — THE shared streaming fast path.

Reference parity (SURVEY.md §4.2 "load points shard"): Harp mappers
streamed their HDFS split through memory while the previous block was
being consumed; the TPU-native equivalent is a bounded multi-stage host
pipeline in front of the device.  Before this module each data-bound app
owned a bespoke loop (`kmeans_stream`'s double buffer; rf/mlp/fileformat
shipped whole arrays synchronously), and the measured 1B-point walls were
host-side: kmeans_ingest ran with ingest_bound_fraction 0.89 (1× v5e,
2026-08-01, BASELINE.md) — the device was already hidden, so the
remaining speed lives entirely in the serial host
read→parse→pad→quantize→device_put chain.  DrJAX
(arXiv:2403.07128) is the reference shape for reusable sharded data
movement; EQuARX (arXiv:2506.17615) motivates the int8/bf16 wire the
pipeline carries for its quantizing users.

:class:`IngestPipeline` runs the host stages as a bounded pipeline:

- **read** (thread pool, submission order): disk slice / file block /
  parse.  With ``read_threads=1`` (default) calls execute strictly in
  order on one thread, so stateful sequential sources
  (``FileSplits.next_block``) are safe; raise it only for random-access
  sources.  A reader may return a lazy view (np.memmap slice) and defer
  the actual copy to the ship stage — that is the single-copy fast path.
- **prep** (thread pool): pad / quantize / cast — the CPU-bound
  transform that used to serialize inside the dispatch loop.
- **ship** (caller thread): ``device_put``/``shard_array``.  Dispatch is
  async, and with ``depth >= 2`` finished chunks are shipped AHEAD of
  consumption, so chunk j+1's H2D overlaps chunk j's compute.

``depth`` bounds how many chunks exist beyond the one being consumed
(bounded memory, like Harp's fixed-size resource pools).  ``depth=1``
runs every stage inline on the caller thread — the same serial order as
the pre-pipeline loops, kept as the bit-exact anchor (all depths are
bit-exact: the stages are deterministic per chunk and consumption is
in order; only the overlap changes).

**Overlap accounting / stall detector.**  The pipeline times each stage,
the caller's blocked time, and the caller's busy time between chunks.
``overlap_efficiency`` = consumer_s / (consumer_s + wait_s) — of the
caller's loop time, the fraction spent computing rather than waiting on
the pipeline: 1.0 means every chunk was ready when asked; 0.5 means the
caller waited as long as it computed; a pipeline that cannot work ahead
of consumption (the canonical dead pipeline: each read gated on the
previous chunk's consumption) scores well below that despite ``depth >=
2``.  When the consumer granted no meaningful compute windows to hide
under (an idle consumer, a serial run) the score is vacuously 1.0 — no
stall can be claimed where nothing was hideable.  With ``stall_warn``
set, a sub-threshold score emits a ``RuntimeWarning`` so a dead
pipeline cannot silently measure as a working one.  The warning is
OPT-IN because on a single-core host CPU-bound stages cannot overlap by
physics (measured 2026-08-04 on this 1-core CPU host: two threaded
numpy casts take 2.04× one thread's wall), so a low score there is the
hardware, not a bug; the score is always computed and exported either
way.

Every pipeline loop in the repo wraps itself in a flight-recorder
budget (``telemetry.budget(h2d_bytes=…, compiles=0)``, warn mode) so
a re-upload or a recompile inside the loop fails tier-1 instead of
costing chip time.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable


@dataclasses.dataclass
class IngestStats:
    """One :meth:`IngestPipeline.stream` run's timing account.

    ``read_s``/``prep_s`` are stage busy sums (across their threads),
    ``ship_s`` is caller-thread device_put dispatch time, ``wait_s`` is
    caller time blocked on background stages, ``blocked_s`` is TOTAL
    caller time inside the pipeline (the comparable of the old loops'
    "host_s"), ``consumer_s`` is caller busy time between chunks (the
    compute the pipeline hides behind), ``wall_s`` the whole stream.
    """

    chunks: int = 0
    read_s: float = 0.0
    prep_s: float = 0.0
    ship_s: float = 0.0
    wait_s: float = 0.0
    blocked_s: float = 0.0
    consumer_s: float = 0.0
    wall_s: float = 0.0
    depth: int = 1
    stalls: int = 0
    overlap_efficiency: float = 1.0

    def as_dict(self) -> dict:
        return {k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in dataclasses.asdict(self).items()}


class IngestPipeline:
    """Bounded multi-stage host→device chunk pipeline (module doc).

    ``read(j)`` → raw chunk j; ``prep(raw)`` → host arrays (None =
    identity); ``ship(host)`` → device arrays (None = host-only
    pipeline).  :meth:`stream` yields chunk 0..n-1 in order; ``stats``
    holds the latest run's :class:`IngestStats`.  Reusable across
    epochs (thread pools persist); use as a context manager or call
    :meth:`close` to reap the pools.
    """

    def __init__(self, read: Callable[[int], Any],
                 prep: Callable[[Any], Any] | None = None,
                 ship: Callable[[Any], Any] | None = None, *,
                 depth: int = 2, read_threads: int = 1,
                 prep_threads: int = 1, tag: str = "ingest",
                 stall_warn: float | None = None,
                 stall_min_hideable_s: float = 0.005):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if read_threads < 1 or prep_threads < 1:
            raise ValueError("read_threads/prep_threads must be >= 1")
        self._read, self._prep, self._ship = read, prep, ship
        self.depth = int(depth)
        self.tag = tag
        self._read_threads = int(read_threads)
        self._prep_threads = int(prep_threads)
        self._stall_warn = stall_warn
        self._stall_min_s = float(stall_min_hideable_s)
        self._read_pool: ThreadPoolExecutor | None = None
        self._prep_pool: ThreadPoolExecutor | None = None
        self._lock = threading.Lock()
        self.stats = IngestStats(depth=self.depth)

    # -- lifecycle ----------------------------------------------------

    def __enter__(self) -> "IngestPipeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Reap the stage thread pools (idempotent)."""
        for pool in (self._read_pool, self._prep_pool):
            if pool is not None:
                pool.shutdown(wait=False, cancel_futures=True)
        self._read_pool = self._prep_pool = None

    def _pools(self):
        if self._read_pool is None:
            self._read_pool = ThreadPoolExecutor(
                self._read_threads, thread_name_prefix=f"{self.tag}-read")
        if self._prep_pool is None and self._prep is not None:
            self._prep_pool = ThreadPoolExecutor(
                self._prep_threads, thread_name_prefix=f"{self.tag}-prep")
        return self._read_pool, self._prep_pool

    # -- streaming ----------------------------------------------------

    def stream(self, n_chunks: int):
        """Yield device (or host) chunks 0..n_chunks-1 in order."""
        self.stats = IngestStats(depth=self.depth)
        if self.depth <= 1:
            return self._stream_serial(n_chunks)
        return self._stream_threaded(n_chunks)

    def _timed_ship(self, x):
        if self._ship is None:
            return x
        t0 = time.perf_counter()
        out = self._ship(x)
        self.stats.ship_s += time.perf_counter() - t0
        return out

    def _stream_serial(self, n: int):
        """depth=1: every stage inline, caller order — the serial-stage
        anchor (bit-exact with the threaded modes by construction)."""
        st = self.stats
        t_wall = time.perf_counter()
        last_out = None
        try:
            for j in range(n):
                t_in = time.perf_counter()
                if last_out is not None:
                    st.consumer_s += t_in - last_out
                t0 = time.perf_counter()
                cur = self._read(j)
                # lock ONLY the accumulation, never the read/prep work
                # itself: the pool paths take self._lock for these same
                # counters, and holding it across a stage (or a ship
                # dispatch) would be harplint HL404
                with self._lock:
                    st.read_s += time.perf_counter() - t0
                if self._prep is not None:
                    t0 = time.perf_counter()
                    cur = self._prep(cur)
                    with self._lock:
                        st.prep_s += time.perf_counter() - t0
                cur = self._timed_ship(cur)
                st.chunks += 1
                last_out = time.perf_counter()
                st.blocked_s += last_out - t_in
                yield cur
        finally:
            st.wall_s = time.perf_counter() - t_wall
            self._finalize(st)

    def _stream_threaded(self, n: int):
        st = self.stats
        read_pool, prep_pool = self._pools()
        pending: deque = deque()   # background futures, submission order
        shipped: deque = deque()   # device chunks staged ahead
        submitted = 0
        consumed = 0

        def timed_read(j):
            t0 = time.perf_counter()
            out = self._read(j)
            with self._lock:
                st.read_s += time.perf_counter() - t0
            return out

        def chained_prep(rf):
            def run():
                raw = rf.result()   # stage handoff; not counted as busy
                t0 = time.perf_counter()
                out = self._prep(raw)
                with self._lock:
                    st.prep_s += time.perf_counter() - t0
                return out
            return run

        def pump():
            nonlocal submitted
            while submitted < n and submitted - consumed < self.depth:
                rf = read_pool.submit(timed_read, submitted)
                pending.append(prep_pool.submit(chained_prep(rf))
                               if self._prep is not None else rf)
                submitted += 1

        t_wall = time.perf_counter()
        last_out = None
        try:
            for j in range(n):
                t_in = time.perf_counter()
                if last_out is not None:
                    st.consumer_s += t_in - last_out
                pump()
                if shipped:
                    cur = shipped.popleft()
                else:
                    f = pending.popleft()
                    t0 = time.perf_counter()
                    raw = f.result()
                    st.wait_s += time.perf_counter() - t0
                    cur = self._timed_ship(raw)
                # ship-ahead: start the async H2D of already-prepped
                # chunks so their transfer rides under the consumer's
                # compute (depth bounds the staged device memory)
                while (pending and pending[0].done()
                       and len(shipped) < self.depth - 1):
                    shipped.append(self._timed_ship(
                        pending.popleft().result()))
                st.chunks += 1
                consumed += 1
                pump()
                last_out = time.perf_counter()
                st.blocked_s += last_out - t_in
                yield cur
        finally:
            st.wall_s = time.perf_counter() - t_wall
            self._finalize(st)

    # -- overlap accounting -------------------------------------------

    def _finalize(self, st: IngestStats) -> None:
        if self.depth >= 2 and st.consumer_s > self._stall_min_s:
            st.overlap_efficiency = max(0.0, min(1.0, (
                st.consumer_s / (st.consumer_s + st.wait_s))))
        else:
            # nothing to hide under (idle consumer, serial mode, or a
            # trivial stream): vacuously efficient, never a stall
            st.overlap_efficiency = 1.0
        if (self._stall_warn is not None
                and st.overlap_efficiency < self._stall_warn):
            st.stalls += 1
            warnings.warn(
                f"ingest pipeline stalled [{self.tag}]: the consumer "
                f"waited {st.wait_s:.3f}s against {st.consumer_s:.3f}s of "
                f"its own compute (overlap_efficiency "
                f"{st.overlap_efficiency:.0%}) — the pipeline is not "
                "working ahead of consumption",
                RuntimeWarning, stacklevel=3)
