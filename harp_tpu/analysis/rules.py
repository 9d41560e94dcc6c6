"""harplint rule registry — every trap gets an id, a layer, and its story.

Reference parity (SURVEY.md §6 has no analogue — Harp shipped no static
analysis at all; correctness discipline lived in code review): the rules
below are the CLAUDE.md "Driver-loop traps" / "Environment" folklore
turned into machine-enforced invariants.  Each rule names the
trap it prevents so a violation message teaches the fix instead of just
rejecting the diff; MIGRATING.md "Running the linter" maps ids to the
original trap prose.

Five layers (see the sibling modules):

- ``HL0xx`` — source AST lints (:mod:`harp_tpu.analysis.astlints`; pure
  ``ast``, no jax import, fast enough for tier-1);
- ``HL1xx`` — jaxpr analyzers (:mod:`harp_tpu.analysis.jaxpr_checks`;
  trace on the CPU backend, zero hardware);
- ``HL2xx`` — Mosaic kernel audit (:mod:`harp_tpu.analysis.mosaic_audit`;
  cross-platform lowering plus jaxpr checks for the silicon limits local
  lowering does NOT enforce);
- ``HL3xx`` — CommGraph communication audit
  (:mod:`harp_tpu.analysis.commgraph`; the static per-call-site
  collective schedule of every registered driver program, cross-checked
  against the CommLedger's trace-time records, plus the use-after-donate
  protocol audit over the serve pipelines);
- ``HL4xx`` — thread-root concurrency audit
  (:mod:`harp_tpu.analysis.threadgraph`; the static thread-root graph of
  the serve/ingest/schedule/timing/fault/bench planes — jax ownership,
  event-loop blocking, shared-state locking, lock-across-dispatch, and
  thread lifecycle — whose ownership map also arms the runtime twin
  :mod:`harp_tpu.utils.threadguard`).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    layer: str          # "ast" | "jaxpr" | "mosaic" | "commgraph" | "threads"
    title: str
    trap: str           # the CLAUDE.md trap this rule machine-checks


RULES: dict[str, Rule] = {r.id: r for r in [
    Rule("HL000", "ast", "unparseable Python source",
         "a file the AST lints cannot parse is a file no rule protects — "
         "fix the syntax error first"),
    Rule("HL001", "ast", "raw XLA collective outside the verb layer",
         "collectives must go through harp_tpu.parallel.collective verbs "
         "(CLAUDE.md conventions) — a raw lax.p*/all_gather/all_to_all "
         "call is invisible to the CommLedger, so bytes-on-wire claims "
         "and the quantized-wire audit silently under-count"),
    Rule("HL002", "ast", "jax.random.PRNGKey in library/driver code",
         "PRNGKey(python_int) specializes the traced program on the seed "
         "— every new seed is a fresh compile; use "
         "utils.prng.key_bits / split_keys (raw uint32[2] via numpy)"),
    Rule("HL003", "ast", "jnp.asarray on host numpy data in ingest paths",
         "jnp.asarray(big_numpy) can ship the array as a compile-time "
         "literal embedded in the executable; use "
         "jax.device_put / mesh.shard_array, the counted ingest entry "
         "points"),
    Rule("HL004", "ast", "jitted driver callable not flight-tracked",
         "a jax.jit program dispatched from a driver loop without "
         "flightrec.track (or a telemetry.budget around the loop) is "
         "invisible to the dispatch/readback budgets — the 20-150 ms "
         "round-trip trap returns as soon as someone loops it"),
    Rule("HL005", "ast", "perf claim without date + chip provenance",
         "perf claims must carry measured numbers with date + chip in "
         "the docstring (CLAUDE.md conventions; see models/kmeans.py for "
         "the form) — an undated number cannot be re-audited after a "
         "toolchain or default flip"),
    Rule("HL101", "jaxpr", "scan-carry gather+DUS copy trap",
         "gathering from a scan-carried table the body also "
         "dynamic_update_slice's makes XLA copy the WHOLE table every "
         "iteration (cost LDA 20 s of a 29 s epoch) — dynamic_slice the "
         "tile first, gather tile-locally"),
    Rule("HL102", "jaxpr", "oversized closed-over constant",
         "a large array baked into the jaxpr as a compile-time constant "
         "is embedded in the executable and its cache entry, and the "
         "program recompiles when it changes — pass it as an argument via "
         "device_put/shard_array"),
    Rule("HL201", "mosaic", "kernel fails Pallas→Mosaic lowering",
         "every registered Pallas kernel must lower via "
         ".trace(...).lower(lowering_platforms=('tpu',)) on the CPU "
         "backend — the no-hardware check that caught three kernels "
         "the chip would have refused, on 2026-07-31"),
    Rule("HL202", "mosaic", "pltpu.prng_seed with >2 seed words",
         "the real TPU toolchain accepts at most TWO seed words (silicon "
         "failure 2026-08-01; local lowering does NOT enforce it) — fold "
         "extra stream ids into a word with an odd-constant multiply + "
         "xor"),
    Rule("HL203", "mosaic", "uint32→float cast inside a kernel",
         "Mosaic has no uint32→f32 cast — shift_right_logical on int32 "
         "instead (the prng-bits→uniform idiom in ops/lda_kernel.py)"),
    Rule("HL204", "mosaic", "block dim -2 not sublane-aligned",
         "a block shape whose second-to-last dim is neither a multiple "
         "of 8 nor the full array dim fails the real Mosaic layout rules "
         "— pad or retile (CLAUDE.md Mosaic limits)"),
    Rule("HL205", "mosaic", "stale kernel work declaration",
         "a kernel-registry vmem_bytes declaration that no longer "
         "matches the kernel's own byte model at the registered shape "
         "mis-prices every perfmodel ranking and memrec VMEM gate "
         "built on it — declarations must sit within memrec.PRESIZE_BAND "
         "of the model (and under the 16 MB/core VMEM ceiling); "
         "re-derive with perfmodel.presize when the kernel changes"),
    Rule("HL301", "commgraph", "collective with no CommLedger record",
         "a collective primitive in a driver jaxpr whose call site has "
         "no trace-time CommLedger record is an untracked wire — every "
         "bytes-on-wire claim the report makes silently under-counts; "
         "route it through a harp_tpu.parallel.collective verb (the "
         "verbs record; raw lax.p* does not)"),
    Rule("HL302", "commgraph", "static byte sheet disagrees with ledger",
         "the statically computed per-shard bytes of a collective site "
         "differ from the CommLedger's trace-time payload for the same "
         "site — one of the two sheets is lying, and the planner/report "
         "numbers built on them are wrong (the kmeans hand-computed "
         "sheet is the cross-check fixture)"),
    Rule("HL303", "commgraph", "use-after-donate on a dispatched buffer",
         "a buffer donated to a dispatch (donate_argnums) was read by "
         "host code or re-dispatched afterwards — the CPU sim ignores "
         "donation so tests stay green, but on TPU the buffer is gone "
         "(the serve ContinuousRunner depth-2 in-flight pipeline is the "
         "motivating case: stage a FRESH buffer per batch, never touch "
         "a donated one)"),
    Rule("HL304", "commgraph", "hoistable loop-invariant collective",
         "a collective inside a scan/fori body whose operands do not "
         "depend on the loop carry or scanned inputs re-ships identical "
         "bytes every iteration — hoist it above the loop (trip count "
         "multiplies the wire for nothing)"),
    Rule("HL401", "threads", "jax touched from a non-owner thread root",
         "a jax-touching call (tracked dispatch, device_put/shard_array, "
         "readback) reachable from a thread root other than the plane's "
         "designated jax owner — the CPU sim tolerates concurrent "
         "runtime access that corrupts state or deadlocks on silicon; "
         "route the work through the owner (the transport dispatcher "
         "thread is the pinned clean fixture)"),
    Rule("HL402", "threads", "blocking call inside the event loop",
         "a blocking call (device round trip, socket recv, unbounded "
         "Queue.get/join/wait, time.sleep) reachable from an event-loop "
         "coroutine and not awaited — a device round trip "
         "freezes every socket the loop owns; await it, bound it, or "
         "move it to the dispatcher thread"),
    Rule("HL403", "threads", "multi-root write with no common lock",
         "shared mutable state (a telemetry spine, scheduler "
         "results/queues, pipeline stats) written from two or more "
         "thread roots with no common lock on the write path — the "
         "spines' single-writer contract becomes a checked invariant "
         "instead of a comment"),
    Rule("HL404", "threads", "lock held across a dispatch/readback",
         "a lock held across a dispatch/readback boundary serializes a "
         "device round trip under the lock — serve-plane "
         "head-of-line blocking; release the lock before touching the "
         "device"),
    Rule("HL405", "threads", "thread with neither daemon nor bounded join",
         "a thread started with neither daemon=True nor a bounded "
         "join(timeout) on a shutdown path hangs process exit when it "
         "blocks — typically inside a device call"),
]}


def rule_ids() -> list[str]:
    return sorted(RULES)
