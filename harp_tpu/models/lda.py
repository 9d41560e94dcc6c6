"""LDA via Collapsed Gibbs Sampling — graded config #3: rotate + push/pull.

Reference parity (SURVEY.md §3.4, §4.4): Harp's ``edu.iu.lda`` samples
topics for a sharded token corpus with the word-topic count table partitioned
across workers; workers either ``pull`` needed rows / ``push`` deltas, or
(rotation variant) rotate word-topic blocks around the ring while a dynamic
scheduler samples the tokens whose words are resident.  Parallel CGS is
*approximate* by construction — workers sample concurrently against slightly
stale counts (Harp's threads do too); convergence is judged by likelihood,
not bitwise equivalence.

TPU-native design:
- tokens pre-partitioned into the (doc-range × word-slice) grid of
  :func:`harp_tpu.models.mfsgd.partition_ratings`-style blocks (2 half-
  slices per worker, pipelined rotation exactly like MF-SGD);
- a rotation step samples all resident tokens in batches: gather doc-topic
  and word-topic count rows, form the CGS posterior
  ``(N_dk+α)(N_wk+β)/(N_k+Vβ)``, sample via Gumbel-argmax (on-device
  ``jax.random``), apply count deltas.  Two delta-application algorithms
  (``LDAConfig.algo``): "dense" one-hot MXU matmuls into dynamic-sliced
  tile blocks (default; 6.3M vs 3.3M tokens/s/chip on the graded config —
  XLA scatter of K-wide rows was 2.2 s of the 2.87 s epoch) and the
  "scatter" reference;
- the global topic-totals vector ``N_k`` is synchronized with an
  ``allreduce`` of deltas every rotation step — the push/pull residue
  (dense K-vector, so psum ≡ push+pull at once);
- chromatic note: within a chunk all tokens sample against the same count
  snapshot (blocked Gibbs); chunk boundaries refresh counts, mirroring the
  granularity Harp gets from its timer-bounded scheduler.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from harp_tpu.ops.pallas_compat import interpret_default
from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import WorkerMesh, current_mesh
from harp_tpu.parallel.rotate import (ROTATE_WIRES, resident_chunk_index,
                                      rotate_pipeline,
                                      rotate_pipeline_resident)
from harp_tpu.models.mfsgd import (
    _ceil_div,
    _dense_bounds,
    algo_kwargs,
    carry_tile_switch,
    partition_ratings,
    partition_ratings_tiles,
    rotate_chunks_resolved,
)
from harp_tpu.utils import flightrec, prng, skew, telemetry


@dataclasses.dataclass
class LDAConfig:
    n_topics: int = 100
    alpha: float = 0.1  # doc-topic Dirichlet prior
    beta: float = 0.01  # word-topic Dirichlet prior
    # Count-update algorithm.  "dense" (default) groups tokens into
    # (d_tile × w_tile) sub-tiles and applies count deltas as one-hot MXU
    # matmuls into dynamic-sliced table blocks — no XLA scatter.  Profiled
    # on the graded config (1k topics, 10M tokens, 1× v5e, 2026-07-30):
    # the two scatters were 2.2 s of the 2.87 s epoch (~25 GB/s scatter
    # floor), while the take-gathers cost only 0.23 s and stay as takes.
    # "scatter" keeps the direct formulation as the readable reference.
    # "pushpull" is Harp's OTHER edu.iu.lda variant (SURVEY.md §4.4):
    # the word-topic table stays row-sharded (never rotated, never
    # materialized); each chunk pulls the word rows its tokens touch
    # (table.pull_rows_sparse), samples, and pushes the deltas back
    # (push_rows_sparse).  The exchange travels in [nw, pull_cap, K]
    # capacity buffers, so wire is O(nw·pull_cap) per chunk — independent
    # of TABLE size (the point: the right variant when the word-topic
    # table outgrows one chip's HBM), but nw× the touched rows at the
    # zero-drop default cap; size pull_cap ≈ chunk/nw when drops are
    # acceptable.
    # Delta matmuls are EXACT in bf16 (operands are 0/±1; f32 accumulate),
    # so counts remain integers on all paths.
    # "pallas" (default since 2026-08-01, FLIP_DECISIONS.jsonl) is the
    # fused kernel of ops/lda_kernel.py: exprace draw over hardware
    # random bits, exact count gathers, the doc-tile carry.
    algo: str = "pallas"
    d_tile: int = 512   # dense: doc-topic tile rows
    w_tile: int = 512   # dense: word-topic tile rows
    # dense: max tokens per tile entry (an overfull tile splits into
    # several).  pallas: the same entries are cut into the kernel's
    # 128-slot chunks and only the chunks that hold tokens are staged
    # (ops/lda_kernel.stage_chunk_list), so the cap no longer sets what a
    # sweep executes; it bounds the host's intermediate entry arrays
    entry_cap: int = 2048
    chunk: int = 8192   # scatter/pushpull: tokens sampled per count-snapshot
    # pushpull: row-request slots per (worker, owner) pair and chunk.  The
    # default (= chunk) guarantees zero drops (a chunk can never request
    # more rows than it has tokens); lower caps shrink the all_to_all
    # buffers ([nw·cap, K] each way) at the cost of counted drops —
    # dropped tokens simply keep their topic that sweep (still a valid
    # Gibbs chain: skipping a site preserves the stationary distribution).
    # SIZING: with dedup_pulls the exact zero-drop cap
    # is the max count of DISTINCT word rows per (chunk, owner) —
    # :func:`suggest_pull_cap` computes it from the loaded corpus (Zipf
    # corpora: far below chunk, because every repeat of a hot word shares
    # one slot); without dedup it is the max TOKEN count per (chunk,
    # owner), which a frequency-sorted Zipf vocabulary pushes toward the
    # whole chunk on the hot owner.
    pull_cap: int | None = None
    # pushpull: collapse duplicate word rows within a chunk to ONE wire
    # request/push slot (duplicates of "the" share a slot; deltas are
    # pre-summed host→owner).  Bit-identical to the non-dedup exchange at
    # zero drops (pulled values equal; delta sums are exact ±1 integers in
    # f32) and strictly fewer drops under any cap, so the default is on.
    # Measured (8-worker CPU sim, Zipf-1.1 ids over m=4096 requests,
    # 2026-07-30, benchmark.sweep_sparse_capacity): the raw stream still
    # drops 41% at cap = m/4 and needs cap = m for zero drops; the
    # deduped stream reaches ZERO drops at cap = m/4 — 4× smaller
    # exchange buffers at equal fidelity.
    dedup_pulls: bool = True
    # algo="dense": carry the doc-topic tile across its
    # od-run instead of slice+DUS per entry.  Entries are od-major
    # (partition_ratings_tiles sorts tiles u-major), so one od's ~25
    # entries at enwiki shapes (512 docs x 100 tok / 2048-token entries)
    # pay 25x the [K, d_tile] in+out HBM traffic without it; the carry
    # pays it once per run (a lax.cond flushes/loads ONLY on od change —
    # correct under any entry order: the switch always flushes before a
    # region can be re-sliced).  The cond+DUS-on-carry interaction is the
    # CLAUDE.md whole-table-copy trap's neighborhood (a regrouping
    # prototype was reverted there); the HLO holds no whole-table copies
    # and the chain is bit-identical (silicon kernel_equiv_check).
    # Measured 2026-08-01 (1x v5e, FLIP_DECISIONS.jsonl): 1.33x on the
    # pallas stack, where it removes the dominant [K, d_tile] DUS
    # write-back, so it is on there; 1.13x on the dense stack, which by
    # then was no longer the default, so the auto default stays off there.
    # None = "auto per algo", STORED as None and resolved at READ time by
    # :func:`carry_db_resolved` (mirrors MFSGDConfig.tiles() /
    # KMeansConfig._use_pallas — a __post_init__ resolution froze the
    # auto value, so ``dataclasses.replace(LDAConfig(), algo='scatter')``
    # raised and ``replace(..., algo='dense')`` silently enabled the
    # dense carry).  An explicit True on a non-tiled algo still raises.
    # algo="pallas" (PR 32): the carry is the kernel's — one call a
    # document-tile run keeps the run's doc tile in VMEM and XLA slices
    # nothing — so there is no slice-per-entry arm to choose: None and
    # True mean that carry, an explicit False raises.
    carry_db: bool | None = None
    # algo="pallas" only: exact base-256-plane count gathers (single-dot
    # bf16 gathers round counts > 256, perturbing the posterior ~0.4% at
    # enwiki hot-word counts).  Default ON: correctness first.
    # False = single-dot gathers (+0/-2 MXU dots per tile); measured
    # 2026-08-01 (1x v5e, FLIP_DECISIONS.jsonl) 1.08x at the graded shape
    # and 1.05x at hot counts, under the 10% a default has to buy.
    pallas_exact_gathers: bool = True
    # Doc-topic table dtype.  "int16" halves the Ndk HBM footprint — the
    # graded enwiki-1M × 1k-topics config needs 4 GB in f32 vs 2 GB in
    # int16 — and is EXACT: a doc-topic count is
    # bounded by the doc's token count (≪ 32767), and every delta is ±1.
    # Sampling is bit-identical to f32 (tests pin this).  Nwk stays f32:
    # corpus-frequent words exceed the int16 range.
    ndk_dtype: str = "float32"
    # Topic draw.  "gumbel" (default): log-posterior + Gumbel noise,
    # argmax — 5 transcendentals per [token, K] element (3 logs + the 2
    # inside the Gumbel transform).  "exprace": competing exponentials —
    # argmin E_k·(nk+Vβ) / ((ndk+α)(nwk+β)) with E_k ~ Exp(1) — draws
    # from the IDENTICAL distribution (the winner of an exponential race
    # at rates p_k is k with probability p_k/Σp) with 1 log + 2 mul +
    # 1 div per element, ~5× fewer transcendentals on the VPU.  Same
    # chain statistics, different random stream.  Default since
    # 2026-08-01 with the pallas algo (its required stack; exprace pays
    # only together with rbg, 1.24x against 0.98x alone on 1x v5e: the
    # noise TENSOR, not the transcendentals, was the wall).
    sampler: str = "exprace"
    # Random-bit source for the per-[token, K] draws.  "threefry"
    # (default): JAX's counter-based PRNG — splittable, reproducible
    # across backends, but ~15 VPU ops per element; at 1k topics the
    # noise tensor is K× the token count, so bit generation is a real
    # share of the epoch.  "rbg": XLA's RngBitGenerator — the TPU
    # hardware generator, near-free, still deterministic per key but a
    # different (backend-dependent) stream.  Chain statistics unaffected
    # (any iid uniform source is a valid Gibbs draw).  Default since
    # 2026-08-01 with the pallas algo (see sampler above).
    rng_impl: str = "rbg"
    # Rotation pipeline knobs (rotation algos only — pushpull never
    # rotates).  Same contract as MFSGDConfig: rotate_chunks None = auto
    # 2 (the historical two-halves schedule, resolved read-time by
    # mfsgd.rotate_chunks_resolved); rotate_wire "exact" | "bf16" |
    # "int8" picks the in-flight chunk's ring payload.  The int8 wire
    # dequantizes counts lossily, so the chain samples against slightly
    # perturbed word-topic counts — a valid approximate-CGS trade (the
    # whole parallel sampler is approximate), not yet measured on a chip:
    # it may become a default only at equal chain likelihood there.
    rotate_chunks: int | None = None
    rotate_wire: str = "exact"

    def __post_init__(self):
        if self.ndk_dtype not in ("float32", "int16"):
            raise ValueError(
                f"ndk_dtype must be 'float32' or 'int16', got {self.ndk_dtype!r}")
        if self.algo not in ("dense", "scatter", "pushpull", "pallas"):
            raise ValueError(
                f"algo must be 'dense', 'scatter', 'pushpull' or "
                f"'pallas', got {self.algo!r}")
        if self.algo == "pallas" and (self.sampler != "exprace"
                                      or self.rng_impl != "rbg"):
            # the fused kernel IS the exprace + hardware-bits stack (see
            # ops/lda_kernel.py) — require the matching knobs so a config
            # never claims a sampler the kernel doesn't run
            raise ValueError(
                "algo='pallas' fuses the exprace draw over hardware "
                "random bits; pass sampler='exprace', rng_impl='rbg'")
        if self.sampler not in ("gumbel", "exprace"):
            raise ValueError(
                f"sampler must be 'gumbel' or 'exprace', got {self.sampler!r}")
        if self.rng_impl not in ("threefry", "rbg"):
            raise ValueError(
                f"rng_impl must be 'threefry' or 'rbg', got {self.rng_impl!r}")
        if self.pull_cap is not None and self.algo != "pushpull":
            raise ValueError("pull_cap only applies to algo='pushpull'")
        # carry_db=None stays None here — :func:`carry_db_resolved` reads
        # it as "on for the pallas stack only" (what 2026-08-01 measured,
        # see the field); only an EXPLICIT True is validated
        if self.carry_db and self.algo not in _TILED_ALGOS:
            raise ValueError("carry_db applies to the tiled algos "
                             f"{_TILED_ALGOS}, not algo={self.algo!r}")
        if self.carry_db is False and self.algo == "pallas":
            raise ValueError(
                "carry_db=False (slice the doc tile per entry) exists for "
                "algo='dense' only: the fused kernel keeps a run's doc "
                "tile in VMEM (a silently-ignored flag wastes sweeps)")
        if self.rotate_chunks is not None and self.rotate_chunks < 1:
            raise ValueError(
                f"rotate_chunks must be >= 1, got {self.rotate_chunks}")
        if self.rotate_wire not in ROTATE_WIRES:
            raise ValueError(
                f"rotate_wire must be one of {ROTATE_WIRES}, "
                f"got {self.rotate_wire!r}")
        if self.algo == "pushpull" and (self.rotate_chunks is not None
                                        or self.rotate_wire != "exact"):
            raise ValueError(
                "rotate_chunks/rotate_wire apply to the rotation algos; "
                "algo='pushpull' never rotates (a silently-ignored "
                "tuning flag wastes benchmark sweeps)")
        if self.pull_cap is not None and self.pull_cap < 1:
            raise ValueError(
                f"pull_cap must be >= 1, got {self.pull_cap} (0 would "
                "silently fall back to the full-chunk default)")


def carry_db_resolved(cfg: LDAConfig) -> bool:
    """Resolved doc-tile carry — ``None`` means "on for the pallas stack
    only" (1x v5e, 2026-08-01: 1.33× on the pallas stack; the dense
    stack's 1.13× came on a stack that is no longer the default, so only
    the kernel stack defaults the carry on).  Read-time resolution
    (mirroring :func:`harp_tpu.models.mfsgd.tiles`) keeps
    ``dataclasses.replace(cfg, algo=...)`` tracking the new algo instead
    of freezing the old algo's resolved value."""
    return cfg.carry_db if cfg.carry_db is not None else cfg.algo == "pallas"


def _cgs_resample(ndk, nwk, nk, z, mask, key, cfg: LDAConfig, vocab_size):
    """The ONE CGS posterior + Gumbel-argmax draw, shared by all three
    algos — a change here (clamps, priors, denominator) applies to
    dense, scatter and pushpull identically."""
    a = jnp.maximum(ndk + cfg.alpha, 1e-10)
    b = jnp.maximum(nwk + cfg.beta, 1e-10)
    c = jnp.maximum(nk + vocab_size * cfg.beta, 1e-10)
    if cfg.rng_impl == "rbg":
        # rebuild the (split-derived, chunk-unique) threefry key as an RBG
        # key: bits then come from the TPU hardware generator instead of
        # ~15 VPU ops/element of counter hashing (see LDAConfig.rng_impl)
        kd = key if key.dtype == jnp.uint32 else jax.random.key_data(key)
        key = jax.random.wrap_key_data(jnp.concatenate([kd, kd]),
                                       impl="rbg")
    if cfg.sampler == "exprace":
        # competing exponentials: argmin_k E_k/p_k lands on k with
        # probability p_k/Σp — the same draw as Gumbel-argmax at ~1/5th
        # the transcendental count (see LDAConfig.sampler)
        e = jax.random.exponential(key, a.shape, a.dtype)
        z_new = jnp.argmin(e * c / (a * b), axis=-1).astype(jnp.int32)
    else:
        logp = jnp.log(a) + jnp.log(b) - jnp.log(c)
        gumbel = jax.random.gumbel(key, logp.shape, logp.dtype)
        z_new = jnp.argmax(logp + gumbel, axis=-1).astype(jnp.int32)
    return jnp.where(mask > 0, z_new, z)


def _sample_chunk(Ndk, Nwk, Nk, z, chunk, key, cfg: LDAConfig, vocab_size):
    """Blocked-Gibbs resample of one token chunk against a count snapshot."""
    d, w, m = chunk  # local doc ids, local word ids, valid mask  [c]
    K = cfg.n_topics

    # remove current assignments from the counts the posterior sees
    # (Ndk may be int16 — see LDAConfig.ndk_dtype; the posterior math is
    # f32 either way and the ±1 delta casts back exactly)
    oh_old = jax.nn.one_hot(z, K, dtype=jnp.float32) * m[:, None]
    ndk = jnp.take(Ndk, d, axis=0).astype(jnp.float32) - oh_old  # [c, K]
    nwk = jnp.take(Nwk, w, axis=0) - oh_old          # [c, K]
    nk = Nk[None, :] - oh_old                        # [c, K]

    z_new = _cgs_resample(ndk, nwk, nk, z, m, key, cfg, vocab_size)

    # apply count deltas (scatter; chunk-granular like Harp's schedulers)
    oh_new = jax.nn.one_hot(z_new, K, dtype=jnp.float32) * m[:, None]
    delta = oh_new - oh_old
    Ndk = Ndk.at[d].add(delta.astype(Ndk.dtype), mode="drop")
    Nwk = Nwk.at[w].add(delta, mode="drop")
    dNk = delta.sum(0)
    return Ndk, Nwk, dNk, z_new


def _sample_chunk_pushpull(Ndk, Nwk_shard, Nk, z, chunk, key,
                           cfg: LDAConfig, vocab_size):
    """Pull → sample → push for one token chunk (Harp's edu.iu.lda
    pull/push variant, SURVEY.md §4.4).

    ``Nwk_shard`` is this worker's row block of the GLOBAL word-topic
    table; the chunk's word rows arrive via ``pull_rows_sparse`` (wire =
    touched rows, the table itself never moves) and the deltas return via
    ``push_rows_sparse``.  A capacity-dropped token keeps its topic this
    sweep — skipping a Gibbs site preserves the stationary distribution —
    and pull-drop ⇒ its delta is zero, so the matching push slot (same
    ids, same bucket order) carries nothing.

    With ``cfg.dedup_pulls`` duplicate word rows in the chunk collapse to
    one request/push slot via :func:`harp_tpu.table.pull_rows_sparse_dedup`
    / ``push_rows_sparse_dedup`` — the Zipf-skew mitigation: per-owner
    capacity need becomes DISTINCT rows touched, not tokens (deltas are
    ±1 integers, so the pre-summed push is bit-identical).  The returned
    drop count is TOKENS skipped this chunk (globally summed), identical
    in meaning across both paths.
    """
    from harp_tpu.table import (pull_rows_sparse, pull_rows_sparse_dedup,
                                push_rows_sparse, push_rows_sparse_dedup)

    d, w, m = chunk  # worker-local doc rows, GLOBAL word ids, valid mask
    K = cfg.n_topics
    cap = cfg.pull_cap if cfg.pull_cap is not None else d.shape[0]
    pull = pull_rows_sparse_dedup if cfg.dedup_pulls else pull_rows_sparse
    push = push_rows_sparse_dedup if cfg.dedup_pulls else push_rows_sparse

    # padding tokens (m == 0) issue no request and take no capacity slot
    rows, ok, _ = pull(Nwk_shard, w, capacity=cap, valid=m > 0)
    # tokens skipped this sweep (drop semantics identical across paths)
    tok_drop = C.allreduce(jnp.sum((m > 0) & ~ok).astype(jnp.int32))

    mm = m * ok.astype(m.dtype)
    oh_old = jax.nn.one_hot(z, K, dtype=jnp.float32) * mm[:, None]
    ndk = jnp.take(Ndk, d, axis=0).astype(jnp.float32) - oh_old
    nwk = rows - oh_old
    nk = Nk[None, :] - oh_old

    z_new = _cgs_resample(ndk, nwk, nk, z, mm, key, cfg, vocab_size)

    oh_new = jax.nn.one_hot(z_new, K, dtype=jnp.float32) * mm[:, None]
    delta = oh_new - oh_old
    Ndk = Ndk.at[d].add(delta.astype(Ndk.dtype), mode="drop")
    # push with the SAME valid mask as the pull (m, not m·ok): the two
    # dedup plans are then identical expressions XLA can CSE into one
    # sort, and the difference is immaterial — a pull-dropped token's
    # delta is zero, so its slot (dropped again, same plan) carries
    # nothing either way
    Nwk_shard, _ = push(Nwk_shard, w, delta, capacity=cap, valid=m > 0)
    dNk = delta.sum(0)
    return Ndk, Nwk_shard, dNk, z_new, tok_drop


def _sample_entry_tiles(Db, Wb, Nk_eff, z, cd, cw, key, cfg: LDAConfig,
                        vocab_size):
    """Tile-level core of :func:`_sample_entry`: resample one entry's
    tokens against pre-sliced ``Db [d_tile, K]`` / ``Wb [w_tile, K]``
    blocks and return the updated blocks — no table slicing here, so the
    ``carry_db`` epoch path can keep a doc block resident across its
    od-run (slicing strategy is the CALLER's concern; the math is shared
    so carry and non-carry chains are bit-identical)."""
    K = cfg.n_topics
    DR, WR = cfg.d_tile, cfg.w_tile
    m = (cd < DR).astype(jnp.float32)
    oh_old = jax.nn.one_hot(z, K, dtype=jnp.float32) * m[:, None]
    ndk = jnp.take(Db, jnp.minimum(cd, DR - 1), axis=0).astype(
        jnp.float32) - oh_old
    nwk = jnp.take(Wb, jnp.minimum(cw, WR - 1), axis=0) - oh_old
    nk = Nk_eff[None, :] - oh_old

    z_new = _cgs_resample(ndk, nwk, nk, z, m, key, cfg, vocab_size)

    oh_new = jax.nn.one_hot(z_new, K, dtype=jnp.float32) * m[:, None]
    delta = (oh_new - oh_old).astype(jnp.bfloat16)  # entries ∈ {-1,0,1}: exact
    ohd = jax.nn.one_hot(cd, DR, dtype=jnp.bfloat16)  # pad rows all-zero
    ohw = jax.nn.one_hot(cw, WR, dtype=jnp.bfloat16)
    dot = lambda a, b: lax.dot_general(  # noqa: E731 — contract dim 0 with 0
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    Db = (Db.astype(jnp.float32) + dot(ohd, delta)).astype(Db.dtype)
    Wb = Wb + dot(ohw, delta)
    dNk = delta.astype(jnp.float32).sum(0)
    return Db, Wb, dNk, z_new


def _sample_entry(Ndk, Nwk, Nk, z, entry, key, cfg: LDAConfig, vocab_size):
    """Dense-tile resample of one (d_tile × w_tile) token entry.

    Gathers stay ``jnp.take`` (profiled cheap); the count-delta scatters
    become one-hot matmuls accumulated into dynamic-sliced table blocks
    and written back with ``dynamic_update_slice`` — no XLA scatter.  The
    matmuls are exact (0/±1 operands in bf16, f32 accumulation), so the
    count tables stay integer-valued like the scatter path's.
    """
    cd, cw, od, ow = entry  # tile-local ids + tile offsets
    DR, WR = cfg.d_tile, cfg.w_tile

    # Slice the tile blocks FIRST and gather from them (ids are tile-local):
    # gathering straight from the scan-carried tables while also
    # dynamic-update-slicing them makes XLA insert a full-table copy per
    # entry (profiled: 20 s of a 29 s epoch).  Blocks in, blocks out keeps
    # the tables update-in-place.
    Db = lax.dynamic_slice_in_dim(Ndk, od, DR, 0)
    Wb = lax.dynamic_slice_in_dim(Nwk, ow, WR, 0)
    Db, Wb, dNk, z_new = _sample_entry_tiles(Db, Wb, Nk, z, cd, cw, key,
                                             cfg, vocab_size)
    Ndk = lax.dynamic_update_slice_in_dim(Ndk, Db, od, 0)
    Nwk = lax.dynamic_update_slice_in_dim(Nwk, Wb, ow, 0)
    return Ndk, Nwk, dNk, z_new


def _sample_runs_pallas(NdkT, NwkT, Nk, z, cd, cw, meta, key, cfg: LDAConfig,
                        vocab_size, count_bounds=(None, None)):
    """One rotation step of the fused kernel (ops/lda_kernel.py) on
    TOPIC-MAJOR tables: the resident half-slice's chunk list ``z/cd/cw
    [NCH, cc]`` + ``meta [NCH]`` is one slab of chunks a document-tile
    run, and each run is ONE kernel call that switches its word tiles
    itself and keeps its doc tile, the chain's deltas and ``N_k``'s in
    VMEM.  Both tables go through the calls in place (aliased), so no
    tile is sliced out or written back by XLA.  Chunk-granular snapshots
    (fresher than the XLA entry snapshot); exprace draw over hardware
    bits by construction."""
    from harp_tpu.ops.lda_kernel import cgs_run_update

    R = NdkT.shape[1] // cfg.d_tile
    run_keys = lax.bitcast_convert_type(jax.random.split(key, R), jnp.int32)

    def per_run(a):
        return a.reshape((R, a.shape[0] // R) + a.shape[1:])

    def run_body(st, inp):
        NdkT, NwkT, dNk_acc = st
        r, zc, cdc, cwc, mc, k = inp
        with jax.named_scope("lda.kernel"):
            NdkT, NwkT, z_new, dNk = cgs_run_update(
                NdkT, NwkT, Nk + dNk_acc, zc, cdc, cwc, mc, r, k,
                alpha=cfg.alpha, beta=cfg.beta, vbeta=vocab_size * cfg.beta,
                d_tile=cfg.d_tile, w_tile=cfg.w_tile,
                interpret=interpret_default(),
                exact_gathers=cfg.pallas_exact_gathers,
                ndk_count_bound=count_bounds[0],
                nwk_count_bound=count_bounds[1])
        return (NdkT, NwkT, dNk_acc + dNk), z_new

    (NdkT, NwkT, dNk), z_new = lax.scan(
        run_body, (NdkT, NwkT, jnp.zeros_like(Nk)),
        (jnp.arange(R, dtype=jnp.int32), per_run(z), per_run(cd),
         per_run(cw), per_run(meta), run_keys))
    return NdkT, NwkT, dNk, z_new.reshape(z.shape)


#: algos on the (d_tile × w_tile) tile grid: dense stages its entries as
#: they are, pallas as the list of the chunks that hold their tokens
_TILED_ALGOS = ("dense", "pallas")

def _epoch_device_fn(mesh: WorkerMesh, cfg: LDAConfig, vocab_size: int,
                     count_bounds=(None, None)):
    """Device-view epoch body: every token resampled once.

    Chunked rotation pipeline identical to MF-SGD's (see
    harp_tpu.models.mfsgd._epoch_device_fn): the word-slice splits into
    ``rotate_chunks_resolved(cfg)`` sub-slices — compute on the resident
    chunk while the previously-sampled one is in flight
    (:func:`rotate_pipeline`; the 2-chunk default is the former bespoke
    half-slice schedule, and ``cfg.rotate_wire`` narrows the ring
    payload).  The per-step token pass dispatches on ``cfg.algo``: one
    fused-kernel call a document-tile run, a scan over dense tile
    entries, or one over fixed-size scatter chunks (see
    :func:`_sample_runs_pallas` / :func:`_sample_entry` /
    :func:`_sample_chunk`).

    ``algo="pallas"`` takes and returns both tables TOPIC-MAJOR
    (``[K, docs]``, ``[K, words]``: the layout the kernel reads and
    :class:`LDA` stores, see ``LDA._Nwk``), the word slice's rotation
    chunks being column ranges of it, and runs the same schedule through
    :func:`rotate_pipeline_resident`: the kernel addresses the resident
    chunk inside the whole slice, so no XLA op of a sweep writes a
    buffer the size of the table or of a half-slice (on one worker;
    across workers the in-flight chunk is cut out for its ring hop).
    The other algos stay row-major and row-chunked.
    """
    nc = rotate_chunks_resolved(cfg)
    tiled = cfg.algo in _TILED_ALGOS
    pallas = cfg.algo == "pallas"
    carry_db = carry_db_resolved(cfg)

    def epoch(Ndk, Nwk_slice, Nk, z_grid, *token_args):
        key = token_args[-1][0]
        tokens = token_args[:-1]
        # per-worker tokens touched this sweep — the skew spine's
        # execution counter (utils/skew.py), folded into the epoch
        # outputs so the driver's ONE readback carries it (flight
        # budgets stay 1 dispatch / 1 readback, tests/test_flightrec.py).
        # Unconditional: a telemetry-gated output would make the traced
        # program differ with the flag (zero-cost contract).
        with jax.named_scope("lda.touched"):
            valid = ((tokens[0] < cfg.d_tile) if tiled
                     else (tokens[2] > 0)).sum()
            work_w = C.allgather(valid.astype(jnp.float32)[None])

        def step(st, computing, t, slot=None):
            Ndk, Nk, z_grid, key = st
            chunk_idx = resident_chunk_index(t, nc)
            with jax.named_scope("lda.slices"):
                blk = jax.tree.map(lambda a: a[chunk_idx], tokens)
                z_blk = z_grid[chunk_idx]
            key, sub = jax.random.split(key)

            if pallas:
                from harp_tpu.ops.lda_kernel import shift_chunk_meta

                cd, cw, meta = blk  # [NCH, cc], [NCH]
                # ``computing`` is the whole topic-major word slice and
                # the chunk list names word tiles of its own half-slice:
                # moved on by the tiles of the slots ahead, the kernel
                # reads and writes that half-slice where it lies
                tiles = computing.shape[1] // nc // cfg.w_tile
                Ndk, computing, dNk, z_new = _sample_runs_pallas(
                    Ndk, computing, Nk, z_blk, cd, cw,
                    shift_chunk_meta(meta, slot * tiles), sub, cfg,
                    vocab_size, count_bounds)
            elif tiled:
                ed, ew, od, ow = blk  # [NE, C], [NE]
                entry_keys = jax.random.split(sub, ed.shape[0])

                if carry_db:
                    # Carry the doc tile across its od-run (entries are
                    # od-major): flush/load rides a lax.cond so an
                    # unchanged od pays ZERO doc-tile HBM traffic.  The
                    # switch always flushes the old region before any
                    # region can be re-sliced, so this is exact under any
                    # entry order — pad entries jumping back to od 0
                    # included.  Same tile core as the non-carry path:
                    # chains are bit-identical (tested).
                    DR = cfg.d_tile

                    def entry_body(st, inp):
                        Ndk, Nwk, dNk_acc, db, cur_od = st
                        cd, cw, zc, eo, wo, k = inp

                        Ndk, db, cur_od = carry_tile_switch(
                            Ndk, db, cur_od, eo, DR, 0)
                        Wb = lax.dynamic_slice_in_dim(
                            Nwk, wo, cfg.w_tile, 0)
                        db, Wb, dNk, z_new = _sample_entry_tiles(
                            db, Wb, Nk + dNk_acc, zc, cd, cw, k,
                            cfg, vocab_size)
                        Nwk = lax.dynamic_update_slice_in_dim(
                            Nwk, Wb, wo, 0)
                        return (Ndk, Nwk, dNk_acc + dNk, db, cur_od), z_new

                    od0 = od[0]
                    db0 = lax.dynamic_slice_in_dim(Ndk, od0, DR, 0)
                    (Ndk, computing, dNk, db_f, od_f), z_new = lax.scan(
                        entry_body,
                        (Ndk, computing, jnp.zeros_like(Nk), db0, od0),
                        (ed, ew, z_blk, od, ow, entry_keys),
                    )
                    # final flush: the last run's tile is still in carry
                    Ndk = lax.dynamic_update_slice_in_dim(
                        Ndk, db_f, od_f, 0)
                else:
                    def entry_body(st, inp):
                        Ndk, Nwk, dNk_acc = st
                        cd, cw, zc, eo, wo, k = inp
                        Ndk, Nwk, dNk, z_new = _sample_entry(
                            Ndk, Nwk, Nk + dNk_acc, zc, (cd, cw, eo, wo),
                            k, cfg, vocab_size)
                        return (Ndk, Nwk, dNk_acc + dNk), z_new

                    (Ndk, computing, dNk), z_new = lax.scan(
                        entry_body, (Ndk, computing, jnp.zeros_like(Nk)),
                        (ed, ew, z_blk, od, ow, entry_keys),
                    )
            else:
                d_blk, w_blk, m_blk = blk
                # clamp to the static block width (blocks narrower than
                # cfg.chunk arise on small corpora — see partition_ratings)
                c = min(cfg.chunk, d_blk.shape[0])
                nchunk = d_blk.shape[0] // c
                chunk_keys = jax.random.split(sub, nchunk)

                def chunk_body(st, inp):
                    Ndk, Nwk, dNk_acc = st
                    d, w, m, zc, k = inp
                    Ndk, Nwk, dNk, z_new = _sample_chunk(
                        Ndk, Nwk, Nk + dNk_acc, zc, (d, w, m), k, cfg,
                        vocab_size)
                    return (Ndk, Nwk, dNk_acc + dNk), z_new

                (Ndk, computing, dNk), z_new = lax.scan(
                    chunk_body, (Ndk, computing, jnp.zeros_like(Nk)),
                    (d_blk.reshape(nchunk, c), w_blk.reshape(nchunk, c),
                     m_blk.reshape(nchunk, c), z_blk.reshape(nchunk, c),
                     chunk_keys),
                )
                z_new = z_new.reshape(-1)
            # push/pull residue: topic totals sync via psum of deltas
            with jax.named_scope("lda.nk"):
                Nk = Nk + C.allreduce(dNk)
            with jax.named_scope("lda.chain"):
                z_grid = z_grid.at[chunk_idx].set(z_new)
            return (Ndk, Nk, z_grid, key), computing

        # the whole pipeline: alone on an op's path it is the rotation's
        # own work (the in-flight chunk cut out and written back, the hop)
        with jax.named_scope("lda.rotate"):
            if pallas:
                (Ndk, Nk, z_grid, key), Nwk_slice = rotate_pipeline_resident(
                    step, (Ndk, Nk, z_grid, key), Nwk_slice,
                    n_chunks=nc, wire=cfg.rotate_wire, chunk_axis=1)
            else:
                (Ndk, Nk, z_grid, key), Nwk_slice = rotate_pipeline(
                    step, (Ndk, Nk, z_grid, key), Nwk_slice,
                    n_chunks=nc, wire=cfg.rotate_wire)
        return Ndk, Nwk_slice, Nk, z_grid, work_w

    return epoch


def _pushpull_epoch_device_fn(mesh: WorkerMesh, cfg: LDAConfig,
                              vocab_size: int):
    """Device-view epoch for ``algo="pushpull"``: no rotation — the
    word-topic table stays row-sharded; each chunk is one
    pull → sample → push round plus a psum of the topic-total deltas
    (Harp's per-iteration pull/push granularity, SURVEY.md §4.4)."""

    def epoch(Ndk, Nwk_shard, Nk, z, d, w, m, keys):
        key = keys[0]
        T = d.shape[0]
        c = min(cfg.chunk, T)
        nchunk = T // c
        chunk_keys = jax.random.split(key, nchunk)

        def body(st, inp):
            Ndk, Nwk_shard, Nk, drop = st
            dc, wc, mc, zc, k = inp
            Ndk, Nwk_shard, dNk, z_new, d_chunk = _sample_chunk_pushpull(
                Ndk, Nwk_shard, Nk, zc, (dc, wc, mc), k, cfg, vocab_size)
            Nk = Nk + C.allreduce(dNk)
            return (Ndk, Nwk_shard, Nk, drop + d_chunk), z_new

        (Ndk, Nwk_shard, Nk, drop), z_new = lax.scan(
            body, (Ndk, Nwk_shard, Nk, jnp.int32(0)),
            (d.reshape(nchunk, c), w.reshape(nchunk, c),
             m.reshape(nchunk, c), z.reshape(nchunk, c), chunk_keys))
        # per-worker valid tokens (the skew execution counter; drops are
        # reported separately and already globally summed)
        work_w = C.allgather(jnp.sum(m > 0).astype(jnp.float32)[None])
        return Ndk, Nwk_shard, Nk, z_new.reshape(-1), drop, work_w

    return epoch


def _device_epoch_fn(mesh: WorkerMesh, cfg: LDAConfig, vocab_size: int,
                     count_bounds=(None, None)):
    """Pick the epoch body for ``cfg.algo`` (rotation vs pull/push)."""
    if cfg.algo == "pushpull":
        return _pushpull_epoch_device_fn(mesh, cfg, vocab_size)
    return _epoch_device_fn(mesh, cfg, vocab_size, count_bounds)


#: the chain's state (Ndk, Nwk, Nk, z_grid) is donated to the sweep
#: programs: the driver installs the outputs in its place, and the
#: program holds no second word-topic table (4 GB at a 1M-word vocabulary
#: and 1k topics; tests/test_chip_compile.py counts what it does hold)
_STATE_ARGS = (0, 1, 2, 3)


def _epoch_in_specs(mesh: WorkerMesh, cfg: LDAConfig):
    """The worker axis lies on a count table's rows (documents, words),
    which are the columns of ``algo="pallas"``'s topic-major tables."""
    table = mesh.spec(1 if cfg.algo == "pallas" else 0)
    return (table, table, P(), mesh.spec(0)) \
        + (mesh.spec(0),) * _n_token_args(cfg)


def make_relayout_fn(mesh: WorkerMesh, to_topic_major: bool):
    """A count table from ``[rows, K]`` to topic-major ``[K, rows]`` or
    back, on the device: every worker's block transposed where it lies."""
    src, dst = mesh.spec(0), mesh.spec(1)
    if not to_topic_major:
        src, dst = dst, src
    return jax.jit(mesh.shard_map(lambda a: a.T, in_specs=(src,),
                                  out_specs=dst))


def _n_token_args(cfg: LDAConfig) -> int:
    # dense: ed/ew/od/ow; pallas: cd/cw/meta; scatter, pushpull: d/w/m
    return 5 if cfg.algo == "dense" else 4  # (+ keys)


def _epoch_out_specs(mesh, cfg):
    """Pushpull epochs also return the global drop counter (replicated);
    every algo appends the replicated per-worker work vector (skew)."""
    base = _epoch_in_specs(mesh, cfg)[:4]  # the state comes back as it went
    return base + ((P(),) if cfg.algo == "pushpull" else ()) + (P(),)


def make_epoch_fn(mesh: WorkerMesh, cfg: LDAConfig, vocab_size: int,
                  count_bounds=(None, None)):
    """Compile one epoch — see :func:`_epoch_device_fn` (rotation algos)
    and :func:`_pushpull_epoch_device_fn`.

    ``count_bounds``: static (max doc-topic, max word-topic) count bounds
    the pallas kernel uses to pick its exact-gather plane counts — chain
    invariants derived by ``LDA._install_pack`` from the initial tables.

    The first four arguments (``Ndk``, ``Nwk``, ``Nk``, ``z_grid``) are
    DONATED (``_STATE_ARGS``): where the backend honours donation a
    handle to them is deleted by the call; keep what the call returns.
    Under ``algo="pallas"`` both tables go in and come out topic-major
    (:func:`epoch_arg_shapes`).
    """
    return jax.jit(
        mesh.shard_map(
            _device_epoch_fn(mesh, cfg, vocab_size, count_bounds),
            in_specs=_epoch_in_specs(mesh, cfg),
            out_specs=_epoch_out_specs(mesh, cfg),
        ),
        donate_argnums=_STATE_ARGS,
    )


def make_multi_epoch_fn(mesh: WorkerMesh, cfg: LDAConfig, vocab_size: int,
                        epochs: int, count_bounds=(None, None)):
    """Compile ``epochs`` Gibbs sweeps as ONE device program.

    Same dispatch-amortization as mfsgd.make_multi_epoch_fn (one
    dispatch and one readback per run, not per sweep).  Each sweep's
    RNG key is derived on device by folding the epoch index into the
    worker's base key, so the chain is identical to per-epoch dispatches
    with the same derivation.  The first four arguments are donated, as
    :func:`make_epoch_fn`'s are.
    """
    inner = _device_epoch_fn(mesh, cfg, vocab_size, count_bounds)

    pp = cfg.algo == "pushpull"

    def many(Ndk, Nwk_slice, Nk, z_grid, *token_args):
        tokens = token_args[:-1]
        base = jax.random.wrap_key_data(token_args[-1][0])

        def body(carry, e):
            st = carry[:4]
            with jax.named_scope("lda.keys"):
                k = jax.random.key_data(jax.random.fold_in(base, e))[None]
            out = inner(*st, *tokens, k)
            if pp:  # accumulate the drop counter across sweeps
                out = out[:4] + (carry[4] + out[4], out[5])
            return out, None

        # trailing zeros: the per-worker work vector's carry slot (the
        # per-sweep counts are identical, so the last sweep's suffice)
        init = (Ndk, Nwk_slice, Nk, z_grid) \
            + ((jnp.int32(0),) if pp else ()) \
            + (jnp.zeros((mesh.num_workers,), jnp.float32),)
        out, _ = lax.scan(body, init, jnp.arange(epochs))
        return out

    return jax.jit(
        mesh.shard_map(
            many,
            in_specs=_epoch_in_specs(mesh, cfg),
            out_specs=_epoch_out_specs(mesh, cfg),
        ),
        donate_argnums=_STATE_ARGS,
    )


def partition_tokens_by_doc(doc_ids, word_ids, z0, n_docs, n_workers,
                            chunk):
    """Partition tokens to their doc-owning worker (pushpull layout).

    Docs are block-partitioned: worker w owns docs [w·d_bound, (w+1)·
    d_bound).  Returns ``(d [n, T_pad] worker-LOCAL doc rows, w [n, T_pad]
    GLOBAL word ids, z [n, T_pad], m [n, T_pad] mask, d_bound)`` with
    T_pad a common multiple of ``min(chunk, T_pad)`` so the epoch scan
    has static chunk shapes.  Padding slots use doc/word 0 with mask 0.
    """
    d_bound = -(-n_docs // n_workers)
    owner = np.asarray(doc_ids) // d_bound
    per = [np.flatnonzero(owner == wk) for wk in range(n_workers)]
    t_max = max((len(p) for p in per), default=0)
    T_pad = max(chunk, -(-t_max // chunk) * chunk) if t_max else chunk
    d = np.zeros((n_workers, T_pad), np.int32)
    w = np.zeros((n_workers, T_pad), np.int32)
    z = np.zeros((n_workers, T_pad), np.int32)
    m = np.zeros((n_workers, T_pad), np.float32)
    for wk, idx in enumerate(per):
        t = len(idx)
        d[wk, :t] = np.asarray(doc_ids)[idx] - wk * d_bound
        w[wk, :t] = np.asarray(word_ids)[idx]
        z[wk, :t] = np.asarray(z0)[idx]
        m[wk, :t] = 1.0
    return d, w, z, m, d_bound


def suggest_pull_cap(word_ids, mask, n_workers, chunk, vocab_size,
                     dedup=True):
    """EXACT zero-drop ``pull_cap`` for a partitioned pushpull layout.

    One host pass over the corpus (load-time, O(T)): for every (worker,
    chunk) slice of the :func:`partition_tokens_by_doc` layout, count the
    requests each owner would receive — DISTINCT word rows when ``dedup``
    (the ``LDAConfig.dedup_pulls`` wire), raw tokens otherwise — and
    return the max.  Sampling with this cap drops nothing; anything
    smaller trades counted drops for smaller [nw·cap, K] buffers.
    The answer is the sizing rule VERDICT r2 item 5 asked for: under
    Zipf word frequencies the deduped cap sits far below ``chunk``
    while the raw cap approaches it (every repeat of a hot word bills
    the hot owner a slot).
    """
    w = np.asarray(word_ids).reshape(n_workers, -1)
    m = np.asarray(mask).reshape(n_workers, -1) > 0
    rows_local = _ceil_div(vocab_size, n_workers)
    T = w.shape[1]
    c = min(chunk, T)
    cap = 1
    for wk in range(n_workers):
        ww = w[wk].reshape(-1, c)
        mm = m[wk].reshape(-1, c)
        for j in range(ww.shape[0]):
            ids = ww[j][mm[j]]
            if dedup:
                ids = np.unique(ids)
            if ids.size:
                cap = max(cap, int(np.bincount(ids // rows_local,
                                               minlength=n_workers).max()))
    return cap


def epoch_arg_shapes(n_workers, n_docs, vocab_size, cfg: LDAConfig,
                     n_tokens=0, entries_per_row=None, entry_width=None):
    """Shape/dtype of every compiled-epoch argument at a given scale,
    WITHOUT building a corpus — ``[(shape, dtype), ...]`` in
    :func:`make_epoch_fn` argument order (Ndk, Nwk, Nk, z, *tokens, keys).
    The two count tables are ``[rows, K]``, a worker's rows one block of
    dim 0, for every algo but ``"pallas"``, whose programs take them as
    :class:`LDA` stores them: topic-major ``[K, rows]``, a worker's rows
    one block of dim 1.

    This is the memory-budget model for graded shapes: the enwiki-1M
    lowering proof (tests/test_lda_scale.py, mirroring the 1B-point
    KMeans proof of tests/test_kmeans_stream.py) feeds these into
    ``jax.ShapeDtypeStruct`` + ``make_multi_epoch_fn(...).lower`` so the
    1M-doc × 1k-topic program is *traced at its true shapes* with zero
    host memory.  SURVEY.md §3.4 #3; VERDICT r2 item 3.

    Corpus-dependent token-layout dims are modeled for an EVENLY
    distributed corpus (the partitioners pad every (worker, slice) block
    to the max-loaded one, so even fill is exact for balanced synthetic
    corpora and a lower bound under skew):

    - scatter/pushpull: per-worker token count pads to a ``cfg.chunk``
      multiple (mirrors :func:`partition_tokens_by_doc` /
      :func:`harp_tpu.models.mfsgd.partition_ratings` exactly);
    - dense: entry width ``entry_width`` defaults to ``cfg.entry_cap``
      (a corpus whose hot tiles fill their caps — enwiki's Zipf vocab
      does; the partitioner shrinks C below the cap only when every tile
      is small) and ``entries_per_row`` defaults to
      ``ceil(tokens_per_grid_row / C)`` — tight packing.  That default
      is a LOWER BOUND, exact only where every occupied tile fills its
      entries: an entry holds ONE (d_tile x w_tile) tile's tokens, so a
      corpus stages at least one entry an occupied tile.
    - pallas: a grid row is one slab of chunks a document-tile run
      (``d_bound / d_tile`` runs, ops/lda_kernel.stage_chunk_list), the
      chunk ``lda_kernel.CHUNK`` slots wide (``entry_width`` is not a
      parameter there and raises); ``entries_per_row`` counts the CHUNKS
      a row, a multiple of the runs, and defaults to tight packing —
      again a LOWER BOUND: a run stages at least one chunk an occupied
      tile, and every run is as long as the longest.  At the source's
      1M-word vocabulary (1,954 word tiles, ~76 tokens a tile) 6,656
      documents stage 13 runs of 1,381 chunks = 17,953 a half-slice
      where tight packing says 13 x 581 = 7,553 (2.4x, 58% of the slots
      padding; the fixed-width entries before PR 32 staged 27x, 96%).
      Pass the real partitioner's count to model a specific corpus
      (tests/test_lda_scale.py does for a Zipf one).
    """
    n, K = n_workers, cfg.n_topics
    ns = rotate_chunks_resolved(cfg) * n  # chunk-slices (pushpull: unused)
    i32, f32 = np.dtype(np.int32), np.dtype(np.float32)
    ndk_dt = np.dtype(cfg.ndk_dtype)
    keys = ((n, 2), np.dtype(np.uint32))
    nk = ((K,), f32)
    if cfg.algo == "pushpull":
        d_bound = _ceil_div(n_docs, n)
        w_own = _ceil_div(vocab_size, n)
        t_max = _ceil_div(n_tokens, n)
        T_pad = max(cfg.chunk, _ceil_div(t_max, cfg.chunk) * cfg.chunk) \
            if t_max else cfg.chunk
        flat = ((n * T_pad,), i32)
        return [((d_bound * n, K), ndk_dt), ((w_own * n, K), f32), nk,
                flat, flat, flat, ((n * T_pad,), f32), keys]
    if cfg.algo in _TILED_ALGOS:
        d_own, w_own, d_bound, ib2 = _dense_bounds(
            n_docs, vocab_size, n, ns, cfg.d_tile, cfg.w_tile)
        tables = [((d_bound * n, K), ndk_dt), ((ib2 * ns, K), f32), nk]
        row_tokens = _ceil_div(n_tokens, n * ns)
        if cfg.algo == "pallas":
            from harp_tpu.ops.lda_kernel import CHUNK

            tables[:2] = [(shape[::-1], dt) for shape, dt in tables[:2]]

            if entry_width is not None:
                raise ValueError("algo='pallas' stages lda_kernel.CHUNK-"
                                 "slot chunks: entry_width is not a "
                                 "parameter")
            runs = d_bound // cfg.d_tile
            NCH = entries_per_row or runs * max(
                1, _ceil_div(_ceil_div(row_tokens, runs), CHUNK))
            if NCH % runs:
                raise ValueError(f"{NCH} chunks a row do not split into "
                                 f"{runs} document-tile runs")
            cc = ((n * ns, NCH, CHUNK), i32)
            return tables + [cc, cc, cc, ((n * ns, NCH), i32), keys]
        C = entry_width or cfg.entry_cap
        NE = entries_per_row or max(1, _ceil_div(row_tokens, C))
        ec, eo = ((n * ns, NE, C), i32), ((n * ns, NE), i32)
        return tables + [ec, ec, ec, eo, eo, keys]
    # scatter: mirrors partition_ratings' B rule
    d_bound = _ceil_div(n_docs, n)
    wb2 = _ceil_div(vocab_size, ns)
    bmax = _ceil_div(n_tokens, n * ns)
    if bmax >= cfg.chunk:
        B = _ceil_div(bmax, cfg.chunk) * cfg.chunk
    else:
        B = min(cfg.chunk, max(8, _ceil_div(bmax, 8) * 8))
    blk = ((n * ns, B), i32)
    return [((d_bound * n, K), ndk_dt), ((2 * wb2 * n, K), f32), nk,
            blk, blk, blk, ((n * ns, B), f32), keys]


class LDA:
    """Host driver (the mapCollective residue for edu.iu.lda)."""

    def __init__(self, n_docs, vocab_size, cfg: LDAConfig | None = None,
                 mesh: WorkerMesh | None = None, seed=0):
        self.mesh = mesh or current_mesh()
        self.cfg = cfg or LDAConfig()
        self.n_docs, self.vocab_size = n_docs, vocab_size
        n = self.mesh.num_workers
        nc = rotate_chunks_resolved(self.cfg)
        # rotate_chunks chunk-slices per worker (rotation algos)
        self._n_slices = nc * n
        if self.cfg.algo in _TILED_ALGOS:
            self.d_own, self.w_own, self.d_bound, wbc = _dense_bounds(
                n_docs, vocab_size, n, self._n_slices,
                self.cfg.d_tile, self.cfg.w_tile)
            self.w_bound = nc * wbc
        elif self.cfg.algo == "pushpull":
            self.d_bound = self.d_own = -(-n_docs // n)
            # word-topic rows this worker OWNS (row-sharded global table)
            self.w_bound = self.w_own = -(-vocab_size // n)
        else:
            self.d_bound = self.d_own = -(-n_docs // n)
            self.w_bound = nc * (-(-vocab_size // self._n_slices))
            self.w_own = self.w_bound // nc
        # (max doc-topic, max word-topic) static count bounds — derived
        # per corpus in _install_pack (pallas only); (None, None) = the
        # kernel falls back to dtype-based gather plane counts
        self._count_bounds = (None, None)
        self._epoch_fn = flightrec.track(
            make_epoch_fn(self.mesh, self.cfg, vocab_size), "lda.epoch")
        if self.cfg.algo == "pallas":
            # between the tables as every reader has them ([rows, K], a
            # worker's rows a block of dim 0) and as they are stored:
            # each worker's block transposed where it lies, on the device
            self._to_topic_major = flightrec.track(
                make_relayout_fn(self.mesh, True), "lda.relayout")
            self._to_row_major = flightrec.track(
                make_relayout_fn(self.mesh, False), "lda.relayout")
        self._multi_fns: dict = {}
        self._seed = seed
        self._tokens = None
        # pushpull only: TOKENS skipped by pull_cap capacity drops in the
        # most recent sample_epoch/sample_epochs call (0 = none skipped)
        self.last_dropped = 0
        # per-worker tokens touched in the most recent sweep (numpy [nw];
        # the skew spine's execution counter — see utils/skew.py)
        self.last_work = None
        # movable pack grains for the skew execution records (PR 15):
        # the elastic driver sets per-worker [(pack_id, load)] lists so
        # the sentinel's skew_trigger plan is whole-unit replayable
        self.skew_units = None

    # The count tables.  ``Ndk`` [docs, K] and ``Nwk`` [words, K] are what
    # every reader gets and every writer gives (storage rows: see
    # :meth:`doc_topic_table` / :meth:`word_topic_table` for external
    # ids).  ``_Ndk`` / ``_Nwk`` are what the device holds and the sweep
    # programs take, donate and return: the same arrays for every algo
    # but "pallas", whose fused kernel reads the tables TOPIC-MAJOR
    # ([K, docs], [K, words], a rotation half-slice a column range), so
    # there they stay topic-major from installation to read-out and a
    # sweep neither transposes nor copies them (at a 1M-word vocabulary
    # the word-topic table is 4 GB, and doing so was a third of the
    # sweep: PERF.md section 6, PR 35).  A read makes a fresh row-major
    # array on the device and keeps none; an assignment goes through the
    # same relayout as installation.
    @property
    def Ndk(self):
        return self._row_major(self._Ndk)

    @Ndk.setter
    def Ndk(self, rows):
        self._Ndk = self._stored(rows)

    @property
    def Nwk(self):
        return self._row_major(self._Nwk)

    @Nwk.setter
    def Nwk(self, rows):
        self._Nwk = self._stored(rows)

    def _row_major(self, table):
        return (self._to_row_major(table) if self.cfg.algo == "pallas"
                else table)

    def _stored(self, rows):
        """A ``[rows, K]`` table, the host's or one on the device, as the
        device stores it.  A host table goes over as it is (the same
        bytes, no copy of it made on the host); under ``algo="pallas"``
        the relayout is one program on the device (12 ms for the 4 GB
        table of the benchmark's cell).  Its input is not donated: a
        transposed table cannot take its place, and a caller that gave a
        device array keeps it; installation's own row-major copy is freed
        as soon as the program has run, until when the device holds the
        table twice."""
        if not isinstance(rows, jax.Array):
            rows = self.mesh.shard_array(rows, 0)
        return (self._to_topic_major(rows) if self.cfg.algo == "pallas"
                else rows)

    def _epoch_args(self):
        """What a sweep program takes, in its argument order."""
        return (self._Ndk, self._Nwk, self.Nk, self.z_grid, *self._tokens,
                self.mesh.shard_array(self._keys, 0))

    def suggest_pull_cap(self, apply=False):
        """Exact zero-drop ``pull_cap`` for the LOADED corpus (pushpull
        only; see module-level :func:`suggest_pull_cap`).  ``apply=True``
        installs it: the epoch program is rebuilt so the next sample
        traces with the new capacity (call between ``set_tokens`` and
        the first sample to avoid a second compile)."""
        if self.cfg.algo != "pushpull":
            raise ValueError("suggest_pull_cap applies to algo='pushpull'")
        if self._tokens is None:
            raise RuntimeError("call set_tokens() before suggest_pull_cap()")
        _, pw, pm = self._tokens
        cap = suggest_pull_cap(pw, pm, self.mesh.num_workers,
                               self.cfg.chunk, self.vocab_size,
                               dedup=self.cfg.dedup_pulls)
        if apply:
            self.cfg.pull_cap = cap
            self._epoch_fn = flightrec.track(
                make_epoch_fn(self.mesh, self.cfg, self.vocab_size),
                "lda.epoch")
            self._multi_fns.clear()
        return cap

    def set_tokens(self, doc_ids, word_ids):
        """Load the token corpus (one entry per token occurrence)."""
        self._install_pack(self.pack_tokens(doc_ids, word_ids))

    def pack_tokens(self, doc_ids, word_ids, z0=None) -> dict:
        """Host-side half of :meth:`set_tokens`: partition the corpus into
        this config's device layout and build the initial count tables —
        a plain dict of numpy arrays (the enwiki-1M pack cost ~675 s on
        a 1-core host, 2026-08-01).  ``_install_pack`` ships it to devices.

        ``z0`` (PR 15): explicit per-token topic assignments instead of
        the seeded random init — the elastic repartition extracts the
        live chain (:meth:`token_state`), remaps doc ids, and repacks
        WITHOUT resetting it; counts rebuild exactly from ``z0``, so
        the move itself is chain-preserving."""
        n = self.mesh.num_workers
        K = self.cfg.n_topics
        with telemetry.span("lda.pack_tokens", tokens=len(doc_ids)):
            with telemetry.span("lda.pack.partition"):
                tokens, z_grid = self._partition_tokens(doc_ids, word_ids,
                                                        z0)
            # initial count tables from the assignments (host, exact)
            with telemetry.span("lda.pack.counts"):
                Ndk = np.zeros((self.d_bound * n, K),
                               np.dtype(self.cfg.ndk_dtype))
                Nwk = np.zeros((self.w_bound * n, K), np.float32)
                gd, gw, gm = self._global_token_ids(tokens)
                gz = z_grid.reshape(-1)
                np.add.at(Ndk, (gd[gm], gz[gm]), 1)  # int: Ndk may be int16
                np.add.at(Nwk, (gw[gm], gz[gm]), 1.0)
                Nk = Nwk.sum(0)
        return {"tokens": tuple(tokens), "z_grid": z_grid, "Ndk": Ndk,
                "Nwk": Nwk, "Nk": Nk, "n_tokens": int(gm.sum())}

    def _partition_tokens(self, doc_ids, word_ids, z0):
        """The layout half of :meth:`pack_tokens`: ``(tokens, z_grid)`` in
        this config's device layout (the MF-SGD grid partitioners, whose
        ``mfsgd.partition.sort`` / ``.pack`` spans nest below the
        caller's ``lda.pack.partition``)."""
        n = self.mesh.num_workers
        K = self.cfg.n_topics
        if self.cfg.ndk_dtype == "int16":
            # a doc-topic count is bounded by the doc's token count; wrap
            # past int16 would corrupt counts SILENTLY (the posterior
            # clamp hides negatives), so fail loudly here instead
            longest = int(np.bincount(np.asarray(doc_ids)).max()) \
                if len(doc_ids) else 0
            if longest > np.iinfo(np.int16).max:
                raise ValueError(
                    f"ndk_dtype='int16': longest document has {longest} "
                    f"tokens > {np.iinfo(np.int16).max} — counts would "
                    "wrap; use ndk_dtype='float32' or split the document")
        # reuse the MF-SGD grid partitioners: "rating value" carries the
        # initial topic assignment
        if z0 is None:
            rng = np.random.default_rng(self._seed)
            z0 = rng.integers(0, K, len(doc_ids)).astype(np.float32)
        else:
            z0 = np.asarray(z0, np.float32)
            if z0.shape != np.shape(doc_ids):
                raise ValueError(
                    f"z0 has shape {z0.shape} but the corpus has "
                    f"{len(doc_ids)} tokens")
        nc = rotate_chunks_resolved(self.cfg)
        if self.cfg.algo in _TILED_ALGOS:
            ed, ew, ez, od, ow, do, wo, db, wbc = partition_ratings_tiles(
                doc_ids, word_ids, z0, self.n_docs, self.vocab_size, n,
                self.cfg.d_tile, self.cfg.w_tile, self.cfg.entry_cap,
                n_slices=self._n_slices,
            )
            assert (do, wo, db, nc * wbc) == (
                self.d_own, self.w_own, self.d_bound, self.w_bound)
            if self.cfg.algo == "pallas":
                # the kernel's layout: the same entries, same order, as
                # the list of the chunks that hold their tokens
                from harp_tpu.ops.lda_kernel import stage_chunk_list

                cd, cw, z_grid, meta = stage_chunk_list(
                    ed, ew, ez, od, ow, db // self.cfg.d_tile,
                    self.cfg.d_tile, self.cfg.w_tile)
                tokens = (cd, cw, meta)
            else:
                z_grid = ez.astype(np.int32)
                tokens = (ed, ew, od, ow)
        elif self.cfg.algo == "pushpull":
            pd, pw, pz, pm, db = partition_tokens_by_doc(
                doc_ids, word_ids, z0, self.n_docs, n, self.cfg.chunk)
            assert db == self.d_bound
            z_grid = pz.reshape(-1)
            tokens = (pd.reshape(-1), pw.reshape(-1), pm.reshape(-1))
        else:
            bd, bw, bz, bm, db, wbc = partition_ratings(
                doc_ids, word_ids, z0, self.n_docs, self.vocab_size, n,
                self.cfg.chunk, n_slices=self._n_slices,
            )
            assert (db, nc * wbc) == (self.d_bound, self.w_bound)
            z_grid = bz.astype(np.int32)
            tokens = (bd, bw, bm)
        return tokens, z_grid

    def _install_pack(self, pack: dict) -> None:
        """Device half of :meth:`set_tokens`: shard a
        :meth:`pack_tokens` dict onto the mesh."""
        n = self.mesh.num_workers
        sh = self.mesh.shard_array
        if self.cfg.algo == "pallas":
            # static count bounds for the kernel's exact gathers (chain
            # invariants: a doc-topic count ≤ its doc length, a
            # word-topic count ≤ its word frequency — Gibbs preserves
            # both row sums).  Enwiki-shape corpora have doc lengths
            # ≤ 256, so the Db gather usually needs ONE bf16 dot instead
            # of 2-3 digit planes.  Epoch program rebuilt: the bounds are
            # trace-time statics.
            # int64 accumulator on the stored dtype — no 2x table copy
            # (an f32 astype of the enwiki int16 Ndk would be 4 GB)
            self._count_bounds = (
                int(np.asarray(pack["Ndk"]).sum(1, dtype=np.int64).max()),
                int(np.asarray(pack["Nwk"]).sum(1, dtype=np.int64).max()))
            self._epoch_fn = flightrec.track(
                make_epoch_fn(self.mesh, self.cfg, self.vocab_size,
                              self._count_bounds), "lda.epoch")
        if telemetry.enabled():
            # ingest-side skew record (host arithmetic over the pack —
            # also fires for cached packs, which skip pack_tokens)
            _, _, gm = self._global_token_ids(pack["tokens"])
            per = gm.reshape(n, -1).sum(1)
            skew.record_partition("lda.partition", per, unit="tokens",
                                  padded_total=gm.size)
            # the record that counts what runs (the twin of
            # ``mfsgd.kernel_slots``): the same tokens over the slots of
            # the arrays AS STAGED — dense: NE x C a grid row, every one
            # of which a sweep executes; pallas: the chunk list, a run's
            # no-op chunks counted (a step each, its body skipped).
            # Through the ledger, not the module hook: the health monitor
            # has judged this per-worker work once already, under
            # "lda.partition"
            staged = pack["tokens"][0]
            skew.ledger.record_partition(
                "lda.kernel_slots", per, unit="tokens",
                padded_total=staged.size)
            if self.cfg.algo == "pallas":
                # what splits that padding: the chunks that hold tokens
                # over the chunks staged.  The rest are the no-ops that
                # end the shorter runs; the padding left after them is
                # inside the chunks that run
                held = gm.reshape(staged.shape).any(-1)
                skew.ledger.record_partition(
                    "lda.kernel_chunks", held.reshape(n, -1).sum(1),
                    unit="chunks", padded_total=held.size)
        placed = (pack["Ndk"], pack["Nwk"], pack["z_grid"], *pack["tokens"])
        with telemetry.span("lda.install",
                            bytes=sum(a.nbytes for a in placed)):
            # the host's tables go over as they are; under algo="pallas"
            # the assignment lays each out topic-major, once, on the
            # device, and the row-major copy is dropped with it
            self.Ndk, self.Nwk = pack["Ndk"], pack["Nwk"]
            self.Nk = jax.device_put(jnp.asarray(pack["Nk"]),
                                     self.mesh.replicated())
            self.z_grid = sh(np.asarray(pack["z_grid"], np.int32), 0)
            self._tokens = tuple(sh(a, 0) for a in pack["tokens"])
        self._multi_fns.clear()  # compiled programs bind to token shapes
        self.n_tokens = int(pack["n_tokens"])
        # raw key bits (utils.prng): bit-identical to split(PRNGKey(seed))
        # without the per-seed PRNGKey compile (CLAUDE.md trap)
        self._keys = prng.split_keys(self._seed, n)

    def _global_token_ids(self, tokens):
        """Grid-local → global STORAGE (doc, word) row ids + valid mask.

        Grid row r belongs to worker ``r // ns`` (doc range) and word
        slice ``r % ns`` (``ns = rotate_chunks · n`` chunk-slices).
        "Storage" rows: the dense layout pads each range to a tile
        multiple, so storage row ≠ external id there (use
        :meth:`doc_topic_table` / :meth:`word_topic_table` for external
        views).
        """
        n = self.mesh.num_workers
        if self.cfg.algo == "pushpull":
            pd, pw, pm = (np.asarray(a) for a in tokens)
            t_pad = pd.shape[0] // n
            gd = pd + (np.arange(n).repeat(t_pad) * self.d_bound)
            return gd, pw, pm > 0  # word ids are already global
        ns = self._n_slices
        db, wbc = self.d_bound, self.w_bound // rotate_chunks_resolved(self.cfg)
        rows = np.arange(n * ns)
        if self.cfg.algo in _TILED_ALGOS:
            if self.cfg.algo == "pallas":
                # a chunk's tile offsets: its run's doc tile (one slab of
                # chunks a run) and the word tile its metadata names
                from harp_tpu.ops.lda_kernel import unpack_chunk_meta

                ed, ew, meta = (np.asarray(a) for a in tokens)
                nchr = ed.shape[1] // (db // self.cfg.d_tile)
                od = np.broadcast_to(
                    np.arange(ed.shape[1]) // nchr * self.cfg.d_tile,
                    meta.shape)
                ow = unpack_chunk_meta(meta)[0] * self.cfg.w_tile
            else:
                ed, ew, od, ow = (np.asarray(a) for a in tokens)
            gm = (ed < self.cfg.d_tile).reshape(-1)
            ld = np.minimum(ed, self.cfg.d_tile - 1) + od[:, :, None]
            lw = np.minimum(ew, self.cfg.w_tile - 1) + ow[:, :, None]
            gd = (ld + (rows // ns * db)[:, None, None]).reshape(-1)
            gw = (lw + (rows % ns * wbc)[:, None, None]).reshape(-1)
            return gd, gw, gm
        bd, bw, bm = (np.asarray(a) for a in tokens)
        gd = (bd + (rows // ns * db)[:, None]).reshape(-1)
        gw = (bw + (rows % ns * wbc)[:, None]).reshape(-1)
        gm = bm.reshape(-1) > 0
        return gd, gw, gm

    def doc_topic_table(self):
        """[n_docs, K] doc-topic counts with storage padding stripped."""
        n = self.mesh.num_workers
        Ndk = np.asarray(self.Ndk)
        if self.cfg.algo in _TILED_ALGOS:
            K = Ndk.shape[-1]
            Ndk = Ndk.reshape(n, self.d_bound, K)[:, : self.d_own].reshape(-1, K)
        return Ndk[: self.n_docs]

    def word_topic_table(self):
        """[vocab_size, K] word-topic counts with storage padding stripped."""
        Nwk = np.asarray(self.Nwk)
        if self.cfg.algo in _TILED_ALGOS:
            K = Nwk.shape[-1]
            wbc = self.w_bound // rotate_chunks_resolved(self.cfg)
            Nwk = Nwk.reshape(self._n_slices, wbc, K)[:, : self.w_own] \
                .reshape(-1, K)
        return Nwk[: self.vocab_size]

    def token_state(self):
        """Current chain state as EXTERNAL ``(doc, word, z)`` token
        triples (PR 15).

        A collapsed-Gibbs chain IS the token-assignment multiset — both
        count tables derive exactly from it — so these triples are the
        complete, layout-independent chain state: the elastic
        repartition extracts them, remaps doc ids, and repacks with
        ``pack_tokens(..., z0=z)``, and the rebuilt counts equal the
        live ones bit-for-bit.  Storage row ids (grid padding included)
        are translated back to external doc/word ids here.
        """
        if self._tokens is None:
            raise RuntimeError("call set_tokens() before token_state()")
        gd, gw, gm = self._global_token_ids(self._tokens)
        gz = np.asarray(self.z_grid).reshape(-1)
        d_st, w_st, z = gd[gm], gw[gm], gz[gm]
        if self.cfg.algo == "pushpull":
            # doc storage is unpadded (d_bound == d_own) and word ids
            # are already global external
            return d_st, w_st, z
        wbc = self.w_bound // rotate_chunks_resolved(self.cfg)
        d_ext = (d_st // self.d_bound) * self.d_own + d_st % self.d_bound
        w_ext = (w_st // wbc) * self.w_own + w_st % wbc
        return d_ext, w_ext, z

    def compile_epochs(self, epochs: int):
        """AOT-compile the ``epochs``-sweep program WITHOUT sampling —
        benchmark warmup must not double the workload (same contract as
        :meth:`harp_tpu.models.mfsgd.MFSGD.compile_epochs`).  The compiled
        executable is cached and reused by :meth:`sample_epochs`."""
        if self._tokens is None:
            raise RuntimeError("call set_tokens() before compile_epochs()")
        fn = self._multi_fns.get(epochs)
        if fn is None:
            jitted = make_multi_epoch_fn(
                self.mesh, self.cfg, self.vocab_size, epochs,
                self._count_bounds)
            # steps=0: lowering traces the sweep's comm sites under the
            # execution tag without counting an execution
            with telemetry.ledger.run("lda.epochs", steps=0), \
                    telemetry.current_names():
                fn = self._multi_fns[epochs] = flightrec.track(
                    jitted.lower(*self._epoch_args()).compile(),
                    "lda.epochs")
        return fn

    def _install_epoch_out(self, out):
        self._Ndk, self._Nwk, self.Nk, self.z_grid = out[:4]
        if self.cfg.algo == "pushpull":
            # drop counter (the "counted, never silently wrong" half of
            # the capacity contract) + per-worker work vector in ONE
            # stacked readback; reading it back doubles as the device sync
            stats = flightrec.readback(jnp.concatenate(
                [out[4].reshape(1).astype(jnp.float32), out[5]]))
            self.last_dropped = int(stats[0])
            self.last_work = np.asarray(stats[1:])
        else:
            # the per-worker work vector rides the epoch outputs; reading
            # it back IS the device sync (replaces the old Nk scalar sync)
            self.last_work = np.asarray(flightrec.readback(out[4]))

    def sample_epochs(self, epochs: int):
        """Run ``epochs`` Gibbs sweeps as one device program (one dispatch,
        one sync) — see :func:`make_multi_epoch_fn`.  Use :meth:`fit` when
        checkpointing between sweeps."""
        fn = self.compile_epochs(epochs)
        args = self._epoch_args()
        # the scan body's traced comm sites execute once per Gibbs sweep
        with telemetry.span("lda.epochs", epochs=epochs), \
                telemetry.ledger.run("lda.epochs", steps=epochs):
            t0 = time.perf_counter()
            out = fn(*args)
            self._advance_keys()
            self._install_epoch_out(out)
            skew.record_execution("lda.epochs", self.last_work,
                                  unit="tokens",
                                  wall_s=time.perf_counter() - t0,
                                  units=self.skew_units)

    def sample_epoch(self):
        if self._tokens is None:
            raise RuntimeError("call set_tokens() before sample_epoch()")
        args = self._epoch_args()
        with telemetry.span("lda.epoch"), \
                telemetry.ledger.run("lda.epochs", steps=1):
            t0 = time.perf_counter()
            out = self._epoch_fn(*args)
            self._advance_keys()
            self._install_epoch_out(out)
            skew.record_execution("lda.epochs", self.last_work,
                                  unit="tokens",
                                  wall_s=time.perf_counter() - t0,
                                  units=self.skew_units)

    def _advance_keys(self):
        # prng.split_keys builds the base key's bits on host — a fresh
        # derived seed per epoch never costs a compile, unlike
        # split(PRNGKey(int)) which specialized per distinct int
        # (CLAUDE.md trap; the bits are identical, so checkpointed
        # chains resume unchanged)
        self._keys = prng.split_keys(int(self._keys[0][0]) ^ 0x9E37,
                                     self.mesh.num_workers)

    def fit(self, epochs: int, ckpt_dir: str | None = None, *,
            ckpt_every: int = 5, max_restarts: int = 3, fault=None):
        """Sample ``epochs`` Gibbs sweeps with optional checkpoint/resume.

        Same recovery contract as :meth:`harp_tpu.models.mfsgd.MFSGD.fit`
        (restart-from-entry-state before the first checkpoint; resume
        installs the restored counts; fault without ckpt_dir is refused).
        The RNG keys are part of the checkpoint, so a recovered run samples
        the same chain it would have without the crash.
        """
        from harp_tpu.utils.fault import check_restored_shapes, fit_epochs

        def get_state():
            return {"Ndk": self.Ndk, "Nwk": self.Nwk, "Nk": self.Nk,
                    "z": self.z_grid, "keys": np.asarray(self._keys)}

        def set_state(state):
            # the state is row-major whatever the device holds (shapes
            # only: no table is made for the comparison)
            flip = -1 if self.cfg.algo == "pallas" else 1
            check_restored_shapes(
                [(name, state[name],
                  jax.ShapeDtypeStruct(held.shape[::flip], held.dtype))
                 for name, held in (("Ndk", self._Ndk), ("Nwk", self._Nwk))]
                + [("z", state["z"], self.z_grid)])
            if not isinstance(state["Ndk"], jax.Array):  # numpy from restore
                sh = self.mesh.shard_array
                # restore casts to the configured dtype (counts are exact
                # integers in either, so f32↔int16 round-trips losslessly)
                self.Ndk = np.asarray(state["Ndk"]).astype(
                    np.dtype(self.cfg.ndk_dtype))
                self.Nwk = np.asarray(state["Nwk"])
                self.z_grid = sh(np.asarray(state["z"]), 0)
                self.Nk = jax.device_put(jnp.asarray(np.asarray(state["Nk"])),
                                         self.mesh.replicated())
            else:
                self.Ndk, self.Nwk = state["Ndk"], state["Nwk"]
                self.Nk, self.z_grid = state["Nk"], state["z"]
            self._keys = np.asarray(state["keys"])

        fit_epochs(self.sample_epoch, get_state, set_state, epochs,
                   ckpt_dir, ckpt_every=ckpt_every,
                   max_restarts=max_restarts, fault=fault,
                   phase="lda.epochs")

    def log_likelihood(self):
        """Mean per-token predictive log-likelihood of current assignments."""
        if self._tokens is None:
            raise RuntimeError("call set_tokens() before log_likelihood()")
        Ndk = np.asarray(self.Ndk)
        Nwk = np.asarray(self.Nwk)
        Nk = np.asarray(self.Nk)
        cfg = self.cfg
        gd, gw, gm = self._global_token_ids(self._tokens)
        gz = np.asarray(self.z_grid).reshape(-1)
        d, w, zz = gd[gm], gw[gm], gz[gm]
        nd = Ndk.sum(1)
        theta = (Ndk[d, zz] + cfg.alpha) / (nd[d] + cfg.n_topics * cfg.alpha)
        phi = (Nwk[w, zz] + cfg.beta) / (Nk[zz] + self.vocab_size * cfg.beta)
        return float(np.mean(np.log(np.maximum(theta * phi, 1e-12))))


def synthetic_corpus(n_docs, vocab_size, n_topics_true, tokens_per_doc, seed=0):
    """Documents generated from a true LDA model (peaked topics)."""
    rng = np.random.default_rng(seed)
    # each true topic owns a disjoint vocabulary band (easy to recover)
    band = vocab_size // n_topics_true
    doc_ids, word_ids = [], []
    for d in range(n_docs):
        topics = rng.dirichlet(np.full(n_topics_true, 0.2))
        zs = rng.choice(n_topics_true, size=tokens_per_doc, p=topics)
        ws = (zs * band + rng.integers(0, band, tokens_per_doc)) % vocab_size
        doc_ids += [d] * tokens_per_doc
        word_ids += ws.tolist()
    return np.asarray(doc_ids, np.int32), np.asarray(word_ids, np.int32)


def _make_cfg(n_topics, algo="dense", chunk=None, d_tile=None, w_tile=None,
              entry_cap=None, pull_cap=None, ndk_dtype="float32",
              dedup_pulls=None, sampler=None, rng_impl=None,
              pallas_exact_gathers=None, carry_db=None,
              rotate_chunks=None, rotate_wire=None):
    """None inherits LDAConfig's defaults; algo-specific knobs raise when
    combined with a non-owning algo (shared contract: mfsgd.algo_kwargs)."""
    # None = "caller didn't say": resolves to the LDAConfig defaults,
    # except algo="pallas" whose fused kernel IS the exprace +
    # hardware-bits stack (an EXPLICIT gumbel/threefry request passes
    # through and errors in LDAConfig's validation)
    if sampler is None:
        sampler = "exprace" if algo == "pallas" else "gumbel"
    if rng_impl is None:
        rng_impl = "rbg" if algo == "pallas" else "threefry"
    # a benchmark or CLI run has the carry only when it asks for it: an
    # unstated carry_db pins to OFF here, so an A/B of the dense carry
    # never compares a run against itself (dense only: scatter/pushpull
    # do not own the knob, and under algo="pallas" the carry is the
    # kernel's since PR 32, where False would raise)
    if carry_db is None and algo == "dense":
        carry_db = False
    return LDAConfig(n_topics=n_topics, ndk_dtype=ndk_dtype, sampler=sampler,
                     rng_impl=rng_impl,
                     **algo_kwargs(algo, {
        ("scatter", "pushpull"): {"chunk": chunk},
        _TILED_ALGOS: {"d_tile": d_tile, "w_tile": w_tile,
                       "entry_cap": entry_cap, "carry_db": carry_db},
        "pushpull": {"pull_cap": pull_cap, "dedup_pulls": dedup_pulls},
        "pallas": {"pallas_exact_gathers": pallas_exact_gathers},
        # rotation pipeline knobs: every rotation algo owns them;
        # pushpull (which never rotates) rejects a non-None value here
        ("dense", "scatter", "pallas"): {"rotate_chunks": rotate_chunks,
                                         "rotate_wire": rotate_wire},
    }))


def benchmark_corpus(n_docs, vocab_size, tokens_per_doc, seed):
    """The deterministic i.i.d. synthetic corpus :func:`benchmark` times
    (structure irrelevant to cost)."""
    rng = np.random.default_rng(seed)
    n_tok = n_docs * tokens_per_doc
    d_ids = np.repeat(np.arange(n_docs, dtype=np.int32), tokens_per_doc)
    w_ids = rng.integers(0, vocab_size, n_tok).astype(np.int32)
    return d_ids, w_ids


def benchmark(n_docs=100_000, vocab_size=50_000, n_topics=1000,
              tokens_per_doc=100, epochs=2, mesh=None, chunk=None, seed=0,
              algo="dense", d_tile=None, w_tile=None, entry_cap=None,
              pull_cap=None, ndk_dtype="float32", dedup_pulls=None,
              sampler=None, rng_impl=None, pallas_exact_gathers=None,
              carry_db=None, rotate_chunks=None, rotate_wire=None):
    """Tokens/sec/chip on an enwiki-1M-scaled config (graded config #3).

    (Full enwiki-1M docs needs a multi-chip pod for the 1M×1k doc-topic
    table; this keeps per-chip load representative.)
    """
    mesh = mesh or current_mesh()
    cfg = _make_cfg(n_topics, algo, chunk, d_tile, w_tile, entry_cap,
                    pull_cap, ndk_dtype, dedup_pulls, sampler, rng_impl,
                    pallas_exact_gathers, carry_db, rotate_chunks,
                    rotate_wire)
    model = LDA(n_docs, vocab_size, cfg, mesh, seed)
    n_tok = n_docs * tokens_per_doc
    d_ids, w_ids = benchmark_corpus(n_docs, vocab_size, tokens_per_doc, seed)
    t0 = time.perf_counter()
    model.set_tokens(d_ids, w_ids)
    prep = time.perf_counter() - t0

    model.sample_epoch()         # warmup + single-epoch compile
    model.compile_epochs(epochs)  # AOT, off-clock, does NOT sample
    t0 = time.perf_counter()
    model.sample_epochs(epochs)  # ONE dispatch + sync for all epochs
    dt = time.perf_counter() - t0
    out = {
        "tokens_per_sec_per_chip": n_tok * epochs / dt / mesh.num_workers,
        "sec_per_epoch": dt / epochs,
        "n_tokens": n_tok, "n_topics": n_topics,
        "prep_sec": prep, "num_workers": mesh.num_workers,
    }
    # Quality field: a sampler or kernel arm must show equal chain
    # quality before it may become a default.
    # Host-side (numpy over all tokens + the full Ndk pull), so skipped at
    # ladder scale — 100M tokens would add minutes of host time and a
    # multi-GB device→host pull to a timing run; the arms that need
    # it all run at the 10M-token default shape.
    if n_tok <= 20_000_000:
        out["log_likelihood"] = model.log_likelihood()
    if algo == "pushpull":
        out["dropped_tokens"] = model.last_dropped  # pull_cap overflow
    return out


def main(argv=None):
    import argparse

    from harp_tpu.utils.metrics import benchmark_json

    p = argparse.ArgumentParser(description="harp-tpu LDA-CGS (edu.iu.lda parity)")
    p.add_argument("--docs", type=int, default=None,
                   help="default: 100000, or max doc id + 1 with --input")
    p.add_argument("--vocab", type=int, default=None,
                   help="default: 50000, or max word id + 1 with --input")
    p.add_argument("--topics", type=int, default=1000)
    p.add_argument("--tokens-per-doc", type=int, default=100)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--algo",
                   choices=["dense", "scatter", "pushpull", "pallas"],
                   default="dense",
                   help="dense: one-hot MXU count updates (fastest, "
                        "default); scatter: direct scatter-add reference; "
                        "pushpull: row-sharded word-topic table, sparse "
                        "pull/push of touched rows (Harp's other edu.iu.lda "
                        "variant; for tables beyond one chip's HBM)")
    p.add_argument("--chunk", type=int, default=None,
                   help="scatter/pushpull: tokens per count-snapshot "
                        "(default 8192); errors under --algo dense")
    p.add_argument("--pull-cap", type=int, default=None,
                   help="pushpull-only: row-request slots per (worker, "
                        "owner) pair (default: chunk — zero drops; "
                        "LDA.suggest_pull_cap computes the exact "
                        "zero-drop cap for a loaded corpus)")
    p.add_argument("--no-dedup-pulls", action="store_true",
                   help="pushpull-only: disable collapsing duplicate "
                        "word rows to one wire slot per chunk (dedup is "
                        "on by default — Zipf corpora need far smaller "
                        "pull_cap with it)")
    p.add_argument("--sampler", choices=["gumbel", "exprace"],
                   default=None,
                   help="topic draw: gumbel (log-posterior + Gumbel "
                        "argmax, default) or exprace (exponential race — "
                        "identical distribution, ~5x fewer VPU "
                        "transcendentals; opt-in until TPU-measured)")
    p.add_argument("--rng-impl", choices=["threefry", "rbg"],
                   default=None,
                   help="random bits for the [token, K] draws: threefry "
                        "(default, splittable counter PRNG) or rbg (TPU "
                        "hardware generator, near-free; opt-in until "
                        "TPU-measured)")
    p.add_argument("--ndk-dtype", choices=["float32", "int16"],
                   default="float32",
                   help="doc-topic table dtype: int16 halves its HBM "
                        "(exact — counts bounded by doc length; the "
                        "enwiki-1M graded config needs 2 GB vs 4 GB)")
    p.add_argument("--d-tile", type=int, default=None,
                   help="dense-only: doc-topic tile rows (default 512)")
    p.add_argument("--w-tile", type=int, default=None,
                   help="dense-only: word-topic tile rows (default 512)")
    p.add_argument("--entry-cap", type=int, default=None,
                   help="dense-only: max tokens per tile entry (default 2048)")
    p.add_argument("--rotate-chunks", type=int, default=None,
                   help="rotation algos: word-slice chunks per worker in "
                        "the chunked rotation pipeline (default 2 — the "
                        "double-buffered two-halves schedule)")
    p.add_argument("--rotate-wire", choices=["exact", "bf16", "int8"],
                   default=None,
                   help="rotation algos: ring payload for in-flight "
                        "chunks (default exact; bf16/int8 halve/quarter "
                        "the rotate bytes, one rounding per hop)")
    p.add_argument("--ckpt-dir", default=None,
                   help="sample with checkpoint/resume instead of "
                        "benchmarking; rerunning with the same dir resumes "
                        "the chain from the latest saved epoch")
    p.add_argument("--ckpt-every", type=int, default=5)
    p.add_argument("--resume", action="store_true",
                   help="assert the run RESUMES from --ckpt-dir: fails "
                        "loudly when the dir holds no checkpoint (a "
                        "mistyped dir must not silently restart the "
                        "chain from epoch 0)")
    p.add_argument("--input", default=None, metavar="FILE_OR_GLOB",
                   help="token files ('doc word [count]' rows) — the Harp "
                        "app's HDFS input; implies sampling mode. --docs/"
                        "--vocab are raised to max id + 1 as needed")
    p.add_argument("--elastic", action="store_true",
                   help="elastic sampling (PR 15): consume mid-run "
                        "skew_trigger findings between sweeps (rebalance "
                        "doc packs, chain preserved) and checkpoint "
                        "mesh-independent state")
    p.add_argument("--max-worker-loss", type=int, default=0,
                   help="elastic: survive up to N permanent worker "
                        "losses by shrinking to the survivors and "
                        "replaying the repartition plan from the last "
                        "checkpoint (implies --elastic; needs --ckpt-dir "
                        "to actually resume)")
    args = p.parse_args(argv)
    from harp_tpu.utils.fault import resolve_resume

    resumed_from = resolve_resume(args.ckpt_dir, args.resume)
    if args.elastic or args.max_worker_loss:
        if args.input:
            raise SystemExit(
                "--elastic currently pairs with the synthetic corpus; "
                "use --docs/--vocab/--tokens-per-doc (file inputs ride "
                "the non-elastic fit)")
        from harp_tpu.elastic.apps import lda_elastic_fit

        n_docs, vocab = args.docs or 100_000, args.vocab or 50_000
        d_ids, w_ids = synthetic_corpus(n_docs, vocab,
                                        max(2, args.topics // 8),
                                        args.tokens_per_doc)
        ad = lda_elastic_fit(
            d_ids, w_ids, n_docs=n_docs, vocab_size=vocab,
            cfg=_make_cfg(args.topics, args.algo, args.chunk,
                          args.d_tile, args.w_tile, args.entry_cap,
                          args.pull_cap, args.ndk_dtype,
                          False if args.no_dedup_pulls else None,
                          args.sampler, args.rng_impl,
                          rotate_chunks=args.rotate_chunks,
                          rotate_wire=args.rotate_wire),
            epochs=args.epochs, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            max_worker_loss=max(args.max_worker_loss, 0))
        print(benchmark_json("lda_elastic_cli", {
            "epochs": args.epochs,
            "log_likelihood": round(ad.metric(), 4),
            "n_workers": ad.mesh.num_workers,
            "worker_losses": ad.losses, "ckpt_dir": args.ckpt_dir}))
        from harp_tpu.report import maybe_emit

        maybe_emit("lda")
        return
    if args.input or args.ckpt_dir:
        if args.input:
            from harp_tpu.native.datasource import load_triples_glob

            try:
                d_ids, w_ids, counts, has_counts = load_triples_glob(args.input)
            except ValueError as e:
                raise SystemExit(str(e))
            if int(d_ids.min()) < 0 or int(w_ids.min()) < 0:
                raise SystemExit(f"{args.input}: negative doc/word ids")
            if has_counts:
                # explicit count column: 0 means "absent" — drop, don't clamp
                reps = np.maximum(counts.astype(np.int64), 0)
            else:
                reps = np.ones(len(d_ids), np.int64)  # bare pair = one token
            d_ids = np.repeat(d_ids, reps)
            w_ids = np.repeat(w_ids, reps)
            if len(d_ids) == 0:
                raise SystemExit(f"{args.input}: all token counts are zero")
            # explicit sizes are raised to fit the data (as the help says)
            n_docs = max(args.docs or 0, int(d_ids.max()) + 1)
            vocab = max(args.vocab or 0, int(w_ids.max()) + 1)
        else:
            n_docs, vocab = args.docs or 100_000, args.vocab or 50_000
            d_ids, w_ids = synthetic_corpus(n_docs, vocab,
                                            max(2, args.topics // 8),
                                            args.tokens_per_doc)
        model = LDA(n_docs, vocab,
                    _make_cfg(args.topics, args.algo, args.chunk,
                              args.d_tile, args.w_tile, args.entry_cap,
                              args.pull_cap, args.ndk_dtype,
                              False if args.no_dedup_pulls else None,
                              args.sampler, args.rng_impl,
                              rotate_chunks=args.rotate_chunks,
                              rotate_wire=args.rotate_wire))
        model.set_tokens(d_ids, w_ids)
        model.fit(args.epochs, args.ckpt_dir, ckpt_every=args.ckpt_every)
        print(benchmark_json("lda_fit_cli", {
            "epochs": args.epochs, "ckpt_dir": args.ckpt_dir,
            "resumed_from": resumed_from,
            "log_likelihood": round(model.log_likelihood(), 4)}))
    else:
        print(benchmark_json("lda_cli", benchmark(
            args.docs or 100_000, args.vocab or 50_000, args.topics,
            args.tokens_per_doc, args.epochs, chunk=args.chunk,
            algo=args.algo, d_tile=args.d_tile,
            w_tile=args.w_tile, entry_cap=args.entry_cap,
            pull_cap=args.pull_cap, ndk_dtype=args.ndk_dtype,
            dedup_pulls=(False if args.no_dedup_pulls
                         else None), sampler=args.sampler,
            rng_impl=args.rng_impl,
            rotate_chunks=args.rotate_chunks,
            rotate_wire=args.rotate_wire)))
    from harp_tpu.report import maybe_emit

    maybe_emit("lda")


if __name__ == "__main__":
    main()
