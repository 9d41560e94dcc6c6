"""``harp serve`` — persistent-mesh inference for the trained apps.

Reference parity (SURVEY.md §1, ROADMAP "harp serve"): Harp has NO serving
path at all — every reference app is batch fit-and-exit, and the "serve
heavy traffic" leg of the north star has no upstream analogue.  This
subsystem is therefore strictly beyond-reference (PARITY.md serving row):
a long-lived server process that loads a trained model through
:class:`harp_tpu.utils.checkpoint.CheckpointManager`, keeps the mesh and
the (sharded) model state device-resident across requests, and answers
inference queries for the trained apps.

The driver-loop traps (CLAUDE.md) are *hard invariants* of the steady
state here, not advice:

- the micro-batcher (:mod:`harp_tpu.serve.batcher`) coalesces queued
  requests into a small ladder of fixed padded shapes, so the steady
  state never sees a new shape → never recompiles
  (``flightrec.budget(compiles=0)`` wraps every batch);
- every batch is ONE dispatch of a cached executable and ONE stacked
  readback (``dispatches=1, readbacks=1`` — engines fold multi-output
  results into a single array on device);
- the AOT executable cache (:mod:`harp_tpu.serve.cache`) persists
  compiled executables to disk keyed by (jax version, topology, shape,
  code fingerprint), so a warm restart performs ZERO XLA compiles before
  its first response (CompileWatch-proven in tests/test_serve.py);
- the continuous plane (:class:`~harp_tpu.serve.server.
  ContinuousRunner` over :class:`~harp_tpu.serve.batcher.
  ContinuousScheduler`, fronted by asyncio TCP in
  :mod:`harp_tpu.serve.transport`) admits requests WHILE batches are in
  flight and dispatches batch t+1 before batch t's readback, so the
  mesh never drains between bursts — same budgets, proven EXACT by
  ``SteadyState.verify_exact``.
"""

from harp_tpu.serve.batcher import (ContinuousScheduler, MicroBatcher,
                                    ShapeLadder)
from harp_tpu.serve.cache import ExecutableCache
from harp_tpu.serve.server import ContinuousRunner, Server

__all__ = ["ContinuousScheduler", "ContinuousRunner", "MicroBatcher",
           "ShapeLadder", "ExecutableCache", "Server"]
