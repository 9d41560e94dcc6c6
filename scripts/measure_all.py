#!/usr/bin/env python
"""Run every graded-config benchmark and record JSONL — the L8 scripts layer.

The reference wraps its canonical configs in shell scripts
(SURVEY.md §2 L8: bin/, test_scripts/); this is the harp-tpu equivalent,
and the protocol behind BASELINE.md's measured rows.

Usage:  python scripts/measure_all.py [--out results.jsonl] [--smoke]
        [--only kmeans mfsgd ...]

--smoke shrinks every config for a fast correctness pass (CPU-safe);
without it the full graded shapes run (real TPU recommended).  Each line
of output is one JSON record with the config, metric, and environment.
"""

import argparse
import datetime
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))  # bench_common

# reusable benchmark artifacts (ingest npy, LDA pack cache) live here
BENCH_DATA = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".bench_data")


def _git_commit() -> str:
    """Short HEAD hash (records must be attributable to exact code)."""
    import subprocess

    try:
        return subprocess.run(
            ["git", "describe", "--always", "--dirty", "--abbrev=7"],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            capture_output=True, text=True, timeout=10).stdout.strip() or "?"
    except Exception:
        return "?"


def _bench_ingest(smoke: bool, quantize=None):
    # shared presets (bench_ingest.run_smoke/run_full) keep this and
    # bench.py's kmeans_ingest config measuring the same shapes; the
    # synthetic compute twin is the sweep-only extra.  quantize="int8"
    # is the int8-WIRE twin (half the host→device bytes — it measured
    # 1.55× on 2026-08-01, when ingest was bound by that day's slow host
    # link (BENCH_local); lossy, so it stays a recommendation for
    # wire-bound links, never a silent default)
    import bench_ingest

    return (bench_ingest.run_smoke(quantize=quantize) if smoke
            else bench_ingest.run_full(compare_synthetic=quantize is None,
                                       quantize=quantize))


# Run order (VERDICT r4 weak #3: scarcity pricing).  Chip time is
# budgeted, and a short run must yield NEW information, so UNMEASURED
# candidates run first —
# their incumbents already have committed BENCH_local rows that
# flip_decision.py compares against — then incumbent re-measures, then
# the ladder/graded-scale shapes.  kmeans_ingest stays last (host-bound
# file generation can only cost itself there).  FIRST_REMEASURE marks the
# candidates/re-measures boundary for the priority test.
FIRST_REMEASURE = "kmeans"
SPRINT_ORDER = [
    # unmeasured candidates (BASELINE.md candidates table)
    "kmeans_int8_fused", "kmeans_stream_int8",
    "mfsgd_pallas", "mfsgd_carry", "mfsgd_chunked_rotate",
    "lda_pallas", "lda_pallas_approx",
    "lda_pallas_hot", "lda_pallas_approx_hot",
    "lda_pallas_carry", "lda_carry", "lda_exprace", "lda_fast",
    "lda_rotate_int8",
    # PR 11: planner-named flip candidates (harp_tpu/plan emits these as
    # fail-closed Plan rows; the schedules exist in code TODAY —
    # collective.allreduce_hier and the bf16 reshard wire — and flip
    # only through flip_decision's gates like every other candidate)
    "kmeans_hier_psum", "lda_planner_wire",
    # PR 6: serving latency/throughput (harp_tpu/serve) — no committed
    # TPU row yet, so they ride the candidates block: the next
    # chip run yields the first serve verdicts (p50/p95/p99 + qps
    # at the graded state shapes); check_jsonl invariant 7 refuses any
    # row whose steady state compiled
    "serve_kmeans", "serve_mfsgd_topk",
    # PR 7: sustained continuous-batching A/B (burst-drain vs
    # admit-while-in-flight on one seeded arrival trace) — the first
    # chip run yields the TPU qps_ratio_vs_burst + queue-depth
    # verdicts; invariant 7's sustained extension refuses rows without
    # offered>=achieved and queue evidence
    "serve_kmeans_sustained", "serve_mfsgd_sustained",
    # PR 8: quantized gradient-wire flip candidates (ROADMAP "decision
    # machinery" item; EQuARX motivates ~2x wire savings) — the DP
    # allreduce rides collective.allreduce_quantized; flip_decision
    # gates on train_acc and the pair is EXCLUSIVE (one grad_wire
    # default).  Defaults stay exact until a chip run measures them.
    "mlp_grad_bf16", "mlp_grad_int8",
    # PR 12: the LAST two per-app wires get measurement paths (ROADMAP
    # planner item) — svm's per-round SV exchange and wdamds's
    # per-iteration coordinate exchange now ride reshard with a wire
    # knob, their drivers are byte-sheeted, and the planner names these
    # configs.  Each pair is EXCLUSIVE (one wire slot per knob); gates:
    # train_acc (svm) / final_stress (wdamds).  Incumbent svm/wdamds
    # rows ride the remaining-apps block below.
    "svm_sv_bf16", "svm_sv_int8",
    "wdamds_coord_bf16", "wdamds_coord_int8",
    # PR 16: the wall-attribution observatory priced the four previously
    # unpriced apps, and each gets ≥1 flip candidate here.  rf's pair is
    # the dense-one-hot-MXU vs scatter histogram A/B (the measured
    # 25 GB/s scatter wall, CLAUDE.md); svm/wdamds flip the STAGED data
    # dtype (the committed 2026-08-01 walls were bound by that day's
    # host→device staging rate, which made halving staged bytes the
    # model's top-ranked lever — not re-measured here); subgraph
    # flips the padded-CSR width (32 columns stage half the bytes of the
    # 64-wide default; the overflow path absorbs the clipped tail).
    "rf_dense_hist", "rf_scatter_hist",
    "svm_x_bf16", "wdamds_delta_bf16", "subgraph_csr32",
    # PR 17: the kernelized arms of the newly priced half — Pallas
    # kernels for svm/wdamds/rf (ops/{svm,wdamds,rf}_kernel.py),
    # presized offline (perfmodel.presize) and Mosaic-proven (HL201)
    # before first silicon contact.  Gates: train_acc (svm/rf) /
    # final_stress (wdamds); rf_hist_pallas is CONDITIONAL on
    # rf_dense_hist holding the hist_algo slot.
    "svm_kernel_pallas", "wdamds_dist_pallas", "rf_hist_pallas",
    # post-compaction subgraph rows (the committed 117.3k vertices/s
    # predates the compact-DP rewrite) + the overflow A/B pairs
    "subgraph_1m", "subgraph_1m_onehot",
    "subgraph_pl", "subgraph_onehot",
    # incumbent re-measures (known numbers, regression check)
    FIRST_REMEASURE, "kmeans_int8", "kmeans_stream",
    "mfsgd", "mfsgd_scatter", "lda", "lda_scatter",
    # ladder / graded-scale / remaining apps
    "lda_scale", "lda_scale_1m", "lda_scale_1m_pallas",
    "mlp", "subgraph", "rf",
    # PR 12: first-ever svm/wdamds rows — the incumbents the new wire
    # candidates' verdicts compare against
    "svm", "wdamds",
    # host-bound ingest: last, outside everyone else's window
    "kmeans_ingest", "kmeans_ingest_int8",
]


def gate_closure(selected) -> set:
    """Expand a candidate selection with every gate partner/anchor the
    verdict machinery needs (PR 13, reusing flip_decision's OWN gate
    tables): a JOINT partner (the knob flips only if every gate flips),
    an EXCLUSIVE partner (the verdict picks the faster — absent rows
    cannot be compared), and a CONDITIONAL anchor (an unmeasured anchor
    vetoes with exit 1).  Pruning that dropped any of these would turn
    a short window into re-run homework; tests pin that it never can.
    """
    import flip_decision

    out = set(selected)
    changed = True
    while changed:
        changed = False
        for group in flip_decision.JOINT_GATES + flip_decision.EXCLUSIVE_GATES:
            if out & set(group) and not set(group) <= out:
                out |= set(group)
                changed = True
        for name, (_, anchor) in flip_decision.CONDITIONAL_GATES.items():
            if name in out and anchor not in out:
                out.add(anchor)
                changed = True
    return out


def predicted_only(top_n: int, topology: str) -> tuple:
    """The perfmodel-pruned ``--only`` list: rank every priceable flip
    candidate by predicted speedup on the chosen topology, keep the top
    N, close over the flip gates, and order by SPRINT_ORDER (the
    unmeasured-candidates-first priority stays exactly as committed —
    the model proposes, the gates and the sprint order dispose).
    Returns (ordered config list, ranked [(cand, speedup)], unpriced).

    FAIL-CLOSED preflight (PR 14, ROADMAP autotuning item 3): before
    the model may prune anything, :func:`harp_tpu.health.grade.
    model_gate` re-runs the perfmodel's self-grade against ALL
    committed evidence — including any rows the last sprint just
    landed.  A ``model_invalidated`` verdict REFUSES the pruning
    (SystemExit 1): a model that fresh silicon evidence contradicts
    must not choose which configs get the next scarce chip run.
    The refusal lifts the moment the model is re-calibrated (the gate
    re-grades live each time; no stale ack file).
    """
    from harp_tpu.perfmodel.cli import _topology, candidate_ranking
    from harp_tpu.perfmodel.grade import latest_tpu_rows

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from harp_tpu.health import grade as health_grade

    ok, finding = health_grade.model_gate(repo)
    if not ok:
        raise SystemExit(
            "measure_all: --predicted-top REFUSED (fail closed): the "
            "perfmodel is INVALIDATED by committed evidence "
            f"({finding.get('failures')} grade failure(s): "
            f"{finding.get('detail')}). Re-calibrate the model and "
            "re-check with `python -m harp_tpu predict --grade` before "
            "pruning a sprint with it.")
    bench = latest_tpu_rows(os.path.join(repo, "BENCH_local.jsonl"))
    ranked, unpriced = candidate_ranking(_topology(topology), bench)
    selected = gate_closure(c for c, _ in ranked[:top_n])
    only = [c for c in SPRINT_ORDER if c in selected]
    return only, ranked, unpriced


def run_all(smoke: bool, only, watchdog=None, skip=None):
    import jax

    from bench_common import SMOKE
    from harp_tpu.models import (kmeans, kmeans_stream, lda, mfsgd, mlp, rf,
                                 subgraph, svm, wdamds)
    from harp_tpu.serve import bench as serve_bench

    # (name, callable) — each returns the model module's benchmark dict
    configs = {
        "kmeans": lambda: kmeans.benchmark(
            **(SMOKE["kmeans"] if smoke else
               {"n": 1_000_000, "d": 300, "k": 100, "iters": 100})),
        # use_pallas=False pins the XLA incumbent arm: the user-facing
        # auto default is the fused kernel since the 2026-08-01 flip,
        # and the A/B identity must not follow it
        "kmeans_int8": lambda: kmeans.benchmark(
            quantize="int8", use_pallas=False,
            **(SMOKE["kmeans"] if smoke else
               {"n": 1_000_000, "d": 300, "k": 100, "iters": 100})),
        # round 3: the FUSED int8 kernel (ops/kmeans_kernel.py) — the XLA
        # int8 path's wall is the ~2 GB/iter [n, k] intermediates it
        # materializes; the kernel never writes them (single HBM pass)
        "kmeans_int8_fused": lambda: kmeans.benchmark(
            quantize="int8", use_pallas=True,
            **(SMOKE["kmeans"] if smoke else
               {"n": 1_000_000, "d": 300, "k": 100, "iters": 100})),
        # PR 11: the planner's hierarchical two-stage psum on the graded
        # kmeans shape (collective.allreduce_hier; Plan rows name this
        # config).  On one chip/host it should read ~1.0x — the win
        # condition is a multi-host mesh — so the verdict doubles as the
        # cost model's honesty check: flip only where topology says to.
        "kmeans_hier_psum": lambda: kmeans.benchmark(
            psum_schedule="hier",
            **(SMOKE["kmeans_hier_psum"] if smoke else
               {"n": 1_000_000, "d": 300, "k": 100, "iters": 100})),
        # north-star shape (SURVEY.md §1): blocked-epoch streaming at
        # 100M×300 k=1000 (full 1B runs via --n on the app CLI)
        "kmeans_stream": lambda: kmeans_stream.benchmark_streaming(
            **(SMOKE["kmeans_stream"] if smoke else
               # calibrate_gen: one extra compile+run isolating the RNG
               # scaffolding a real ingest wouldn't pay (ex-gen rate)
               {"n": 100_000_000, "d": 300, "k": 1000, "iters": 2,
                "chunk_points": 262_144, "calibrate_gen": True})),
        # round 3: the same compute formulation on the int8 MXU (2× the
        # bf16 rate on v5e) — device-quantized chunks, static 5σ scale
        "kmeans_stream_int8": lambda: kmeans_stream.benchmark_streaming(
            quantize="int8",
            **(SMOKE["kmeans_stream"] if smoke else
               {"n": 100_000_000, "d": 300, "k": 1000, "iters": 2,
                "chunk_points": 262_144, "calibrate_gen": True})),
        "mfsgd": lambda: mfsgd.benchmark(
            **(SMOKE["mfsgd"]
               if smoke else {})),
        "mfsgd_scatter": lambda: mfsgd.benchmark(
            algo="scatter",
            **(SMOKE["mfsgd_scatter"] if smoke else {})),
        # round 4: W tile carried across its tou-run (the LDA carry_db
        # lever applied to the dense MF-SGD path); bit-identical chain
        "mfsgd_carry": lambda: mfsgd.benchmark(
            carry_w=True,
            **(SMOKE["mfsgd"] if smoke else {})),
        # round 3: the dense update fused into one VMEM Pallas kernel
        # (ops/mfsgd_kernel.py) — candidate new default if it wins on TPU
        "mfsgd_pallas": lambda: mfsgd.benchmark(
            algo="pallas",
            # smoke tiles must pass the kernel's TPU gate (128-multiples)
            **(SMOKE["mfsgd_pallas"] if smoke else {})),
        # PR 2: the chunked double-buffered rotator at 4 chunks/worker on
        # the flipped pallas stack — finer overlap granularity (quarter
        # slices in flight) than the incumbent 2-chunk schedule; may flip
        # MFSGDConfig.rotate_chunks=4 via flip_decision (quality gate:
        # rmse_final — the visit order changes, the math does not)
        "mfsgd_chunked_rotate": lambda: mfsgd.benchmark(
            algo="pallas", rotate_chunks=4,
            **(SMOKE["mfsgd_pallas"] if smoke else {})),
        "lda": lambda: lda.benchmark(
            **(SMOKE["lda"] if smoke else
               {"pack_cache": BENCH_DATA})),
        # round 4: doc-tile carried across its od-run (one flush/load per
        # run instead of per entry) — the VERDICT r3 item 2 Db-carry, now
        # a flag; bit-identical chain (tested), TPU verdict pending
        "lda_carry": lambda: lda.benchmark(
            carry_db=True,
            **(SMOKE["lda"] if smoke else
               {"pack_cache": BENCH_DATA})),
        # round 3: exponential-race topic draw (identical distribution,
        # ~5× fewer VPU transcendentals) — candidate default if it wins
        "lda_exprace": lambda: lda.benchmark(
            sampler="exprace",
            **(SMOKE["lda"] if smoke else
               {"pack_cache": BENCH_DATA})),
        # round 3: exprace + hardware RNG together — the candidate new
        # default sampling stack; vs lda/lda_exprace it attributes the
        # win between sampler math and bit generation
        "lda_fast": lambda: lda.benchmark(
            sampler="exprace", rng_impl="rbg",
            **(SMOKE["lda"] if smoke else
               {"pack_cache": BENCH_DATA})),
        # round 3: the whole entry fused into one VMEM kernel
        # (ops/lda_kernel.py) — candidate new default if it wins on TPU.
        # round 4: gathers are EXACT by default (base-256 digit planes)
        "lda_pallas": lambda: lda.benchmark(
            algo="pallas",
            **(SMOKE["lda_pallas"] if smoke else
               {"pack_cache": BENCH_DATA})),
        # round 4: the single-dot bf16 gather variant (counts > 256 round
        # ~0.4% in the posterior) — may flip pallas_exact_gathers=False
        # only if ≥10% faster at equal chain likelihood (flip_decision)
        "lda_pallas_approx": lambda: lda.benchmark(
            algo="pallas", pallas_exact_gathers=False,
            **(SMOKE["lda_pallas"] if smoke else
               {"pack_cache": BENCH_DATA})),
        # VERDICT r4 item 7: the exact-vs-approx gather A/B at a shape
        # whose counts EXCEED 256 from initialization (avg Nwk cell =
        # 4M tok / (256 vocab × 32 topics) ≈ 488) — at the default sweep
        # shape counts stay double-digit, so bf16 rounding physically
        # cannot show in the LL and the quality gate would pass vacuously.
        # pallas_exact_gathers=False may flip only if BOTH the
        # default-shape speed gate and THIS LL gate pass (flip_decision).
        "lda_pallas_hot": lambda: lda.benchmark(
            algo="pallas",
            **(SMOKE["lda_pallas"] if smoke else
               {"n_docs": 20_000, "vocab_size": 256, "n_topics": 32,
                "tokens_per_doc": 200, "d_tile": 128, "w_tile": 128,
                "pack_cache": BENCH_DATA})),
        "lda_pallas_approx_hot": lambda: lda.benchmark(
            algo="pallas", pallas_exact_gathers=False,
            **(SMOKE["lda_pallas"] if smoke else
               {"n_docs": 20_000, "vocab_size": 256, "n_topics": 32,
                "tokens_per_doc": 200, "d_tile": 128, "w_tile": 128,
                "pack_cache": BENCH_DATA})),
        # round 4: fused kernel + carried doc tile — the two HBM levers
        # stacked (entry VMEM-residency from the kernel, od-run tile
        # amortization from the carry).  Since PR 32 the carry is the
        # kernel's own (one call a document-tile run): this and
        # `lda_pallas` run one program, the names stay for the gates
        "lda_pallas_carry": lambda: lda.benchmark(
            algo="pallas", carry_db=True,
            **(SMOKE["lda_pallas"] if smoke else
               {"pack_cache": BENCH_DATA})),
        # PR 2: int8 rotate wire on the flipped default stack — quarter
        # the ring bytes per word-slice hop (collective.rotate_quantized;
        # one rounding per hop, but counts dequantize lossily so the
        # chain samples against perturbed word-topic counts — the LL
        # flip gate decides whether quality holds).  Shares the 2-chunk
        # pack cache with lda_pallas_carry (wire is not layout)
        "lda_rotate_int8": lambda: lda.benchmark(
            algo="pallas", carry_db=True, rotate_wire="int8",
            **(SMOKE["lda_pallas"] if smoke else
               {"pack_cache": BENCH_DATA})),
        # PR 11: the planner's bf16 reshard wire on the flipped default
        # stack — half the ring bytes at ONE rounding per hop (better
        # conditioned than int8's lossy count dequant), the middle rung
        # the Plan row prices between exact and int8.  EXCLUSIVE with
        # lda_rotate_int8 in flip_decision: rotate_wire is one knob.
        "lda_planner_wire": lambda: lda.benchmark(
            algo="pallas", carry_db=True, rotate_wire="bf16",
            **(SMOKE["lda_planner_wire"] if smoke else
               {"pack_cache": BENCH_DATA})),
        "lda_scatter": lambda: lda.benchmark(
            algo="scatter",
            **(SMOKE["lda_scatter"] if smoke
               else {"pack_cache": BENCH_DATA})),
        # PR 6: steady-state serving — synthetic state at the graded
        # shapes (kmeans k=100/d=300 centroids; ML-20M-sized factors),
        # single-row requests in bursts: the latency ladder the "serve
        # heavy traffic" north-star leg is graded on.  Self-contained
        # (no checkpoint needed); the AOT cache sits beside the compile
        # cache, so the first run measures a cold start and later ones
        # the warm restart.
        "serve_kmeans": lambda: serve_bench.benchmark(
            app="kmeans",
            **(SMOKE["serve_kmeans"] if smoke else
               {"n_requests": 2048, "rows_per_request": 1,
                "state_shape": {"k": 100, "d": 300}})),
        "serve_mfsgd_topk": lambda: serve_bench.benchmark(
            app="mfsgd", topk=10,
            **(SMOKE["serve_mfsgd_topk"] if smoke else
               {"n_requests": 2048, "rows_per_request": 1,
                "state_shape": {"n_users": 138_493, "n_items": 26_744,
                                "rank": 64}})),
        # PR 7: sustained-load A/B at the same graded state shapes —
        # single-row requests on one seeded trace offered at 2× the
        # calibrated burst capacity (both planes saturated, so policy
        # not arrival luck decides), 4096 requests so the backlog can
        # fill 512-rungs (see the bench_common smoke comment)
        "serve_kmeans_sustained": lambda: serve_bench.benchmark_sustained(
            app="kmeans",
            **(SMOKE["serve_kmeans_sustained"] if smoke else
               {"n_requests": 4096, "rows_per_request": 1,
                "state_shape": {"k": 100, "d": 300}})),
        "serve_mfsgd_sustained": lambda: serve_bench.benchmark_sustained(
            app="mfsgd", topk=10,
            **(SMOKE["serve_mfsgd_sustained"] if smoke else
               {"n_requests": 4096, "rows_per_request": 1,
                "state_shape": {"n_users": 138_493, "n_items": 26_744,
                                "rank": 64}})),
        # ladder configs AFTER the default-shape flip pairs: a run can
        # be cut short, and the priority is the candidates table (a run
        # that ends at minute 40 should have already measured every
        # gated pair)
        # graded-scale ladder (VERDICT r1 item 5): 500k docs × 1k topics
        # with the int16 doc-topic table (2 GB instead of 4 GB at 1M docs)
        "lda_scale": lambda: lda.benchmark(
            **({"n_docs": 512, "vocab_size": 128, "n_topics": 8,
                "tokens_per_doc": 16, "epochs": 1, "d_tile": 16,
                "w_tile": 16, "entry_cap": 64, "ndk_dtype": "int16"}
               if smoke else
               {"n_docs": 500_000, "vocab_size": 50_000, "n_topics": 1000,
                "tokens_per_doc": 100, "epochs": 1, "ndk_dtype": "int16",
                "pack_cache": BENCH_DATA})),
        # TRUE graded shapes (enwiki-1M: 1M docs × 1k topics, 100M tokens,
        # int16 Ndk — fits one chip: 2 GB Ndk + 0.23 GB Nwk; the program
        # is lowering-proven in tests/test_lda_scale.py, this EXECUTES it
        "lda_scale_1m": lambda: lda.benchmark(
            **({"n_docs": 1024, "vocab_size": 128, "n_topics": 8,
                "tokens_per_doc": 16, "epochs": 1, "d_tile": 16,
                "w_tile": 16, "entry_cap": 64, "ndk_dtype": "int16"}
               if smoke else
               {"n_docs": 1_000_000, "vocab_size": 50_000,
                "n_topics": 1000, "tokens_per_doc": 100, "epochs": 1,
                "ndk_dtype": "int16", "pack_cache": BENCH_DATA})),
        # the FLIPPED default stack (pallas+exprace+rbg+carry_db,
        # 2026-08-01) at the true graded shape — the dense arm above
        # measured 5.88M tok/s there; this row is the framework's
        # graded-#3 headline after the flip
        "lda_scale_1m_pallas": lambda: lda.benchmark(
            algo="pallas", carry_db=True,
            **({"n_docs": 1024, "vocab_size": 128, "n_topics": 8,
                "tokens_per_doc": 16, "epochs": 1, "d_tile": 16,
                "w_tile": 16, "entry_cap": 64, "ndk_dtype": "int16"}
               if smoke else
               {"n_docs": 1_000_000, "vocab_size": 50_000,
                "n_topics": 1000, "tokens_per_doc": 100, "epochs": 1,
                "ndk_dtype": "int16", "pack_cache": BENCH_DATA})),
        "mlp": lambda: mlp.benchmark(
            **(SMOKE["mlp"] if smoke else {})),
        # PR 8: the quantized-gradient-wire candidates — same shapes as
        # the incumbent "mlp" row, only the allreduce wire differs, so
        # the A/B isolates wire bytes vs train_acc (flip_decision gate)
        "mlp_grad_bf16": lambda: mlp.benchmark(
            cfg=mlp.MLPConfig(grad_wire="bf16"),
            **(SMOKE["mlp"] if smoke else {})),
        "mlp_grad_int8": lambda: mlp.benchmark(
            cfg=mlp.MLPConfig(grad_wire="int8"),
            **(SMOKE["mlp"] if smoke else {})),
        # PR 12: svm/wdamds incumbents + wire candidates (same shapes as
        # their incumbent so the A/B isolates wire bytes vs quality —
        # train_acc for svm, final_stress for wdamds; EXCLUSIVE pairs
        # in flip_decision, one wire slot per knob).  Full shapes are
        # the apps' graded defaults (svm 500k×128, wdamds n=4096).
        "svm": lambda: svm.benchmark(
            **(SMOKE["svm"] if smoke else {})),
        "svm_sv_bf16": lambda: svm.benchmark(
            sv_wire="bf16", **(SMOKE["svm"] if smoke else {})),
        "svm_sv_int8": lambda: svm.benchmark(
            sv_wire="int8", **(SMOKE["svm"] if smoke else {})),
        # PR 16: bf16-staged X (half the H2D bytes on the staging-bound
        # committed wall; dots promote to f32 so only the stored feature
        # precision changes — train_acc gates the flip)
        "svm_x_bf16": lambda: svm.benchmark(
            x_dtype="bf16", **(SMOKE["svm_x_bf16"] if smoke else {})),
        # PR 17: the fused Pegasos kernel arm (ops/svm_kernel.py) —
        # same shapes as the incumbent "svm" row, only the inner-solve
        # schedule differs (one feature pass per step instead of two;
        # train_acc gates the flip)
        "svm_kernel_pallas": lambda: svm.benchmark(
            algo="pallas",
            **(SMOKE["svm_kernel_pallas"] if smoke else {})),
        "wdamds": lambda: wdamds.benchmark(
            **(SMOKE["wdamds"] if smoke else {})),
        "wdamds_coord_bf16": lambda: wdamds.benchmark(
            coord_wire="bf16", **(SMOKE["wdamds"] if smoke else {})),
        "wdamds_coord_int8": lambda: wdamds.benchmark(
            coord_wire="int8", **(SMOKE["wdamds"] if smoke else {})),
        # PR 16: bf16-staged dissimilarity matrix (the n² delta is the
        # dominant staged buffer; final_stress gates the flip)
        "wdamds_delta_bf16": lambda: wdamds.benchmark(
            delta_dtype="bf16",
            **(SMOKE["wdamds_delta_bf16"] if smoke else {})),
        # PR 17: the fused SMACOF kernel arm (ops/wdamds_kernel.py) —
        # same shapes as the incumbent "wdamds" row, only the Guttman
        # step schedule differs (D/ratio stay in VMEM; final_stress
        # gates the flip)
        "wdamds_dist_pallas": lambda: wdamds.benchmark(
            algo="pallas",
            **(SMOKE["wdamds_dist_pallas"] if smoke else {})),
        "subgraph": lambda: subgraph.benchmark(
            **(SMOKE["subgraph"] if smoke else {})),
        # PR 16: half-width padded CSR on the graded uniform graph — the
        # staged adjacency halves, the clipped tail rides the exact
        # overflow segment path (estimate equality gates the flip)
        "subgraph_csr32": lambda: subgraph.benchmark(
            max_degree=32,
            **(SMOKE["subgraph_csr32"] if smoke else {})),
        # overflow-tail A/B pair (r2 verdict item 7): POWERLAW graph so
        # the tail carries real mass (the uniform graded config's
        # ~Poisson(16) degrees never exceed max_degree=64 — segment vs
        # onehot would execute identical work and the A/B would read
        # 1.0x at any truth); identical counts by construction —
        # flip_decision compares the rates and asserts the estimates
        # match to 1e-6 before overflow_algo may change default
        "subgraph_pl": lambda: subgraph.benchmark(
            graph="powerlaw", max_degree=16,
            **(SMOKE["subgraph"] if smoke else {})),
        "subgraph_onehot": lambda: subgraph.benchmark(
            graph="powerlaw", max_degree=16, overflow_algo="onehot",
            **(SMOKE["subgraph"] if smoke else {})),
        # the graded template at graded scale (VERDICT r2 item 4): u5-tree
        # on a 1M-vertex power-law graph — hub mass rides the exact
        # overflow segment-sum path (overflow_share reported; 0 dropped)
        "subgraph_1m": lambda: subgraph.benchmark(
            graph="powerlaw",
            **({**SMOKE["subgraph"], "max_degree": 8}
               if smoke else
               {"n_vertices": 1_000_000, "avg_degree": 8,
                "max_degree": 16, "template": "u5-tree"})),
        "subgraph_1m_onehot": lambda: subgraph.benchmark(
            graph="powerlaw", overflow_algo="onehot",
            **({**SMOKE["subgraph"], "max_degree": 8}
               if smoke else
               {"n_vertices": 1_000_000, "avg_degree": 8,
                "max_degree": 16, "template": "u5-tree"})),
        "rf": lambda: rf.benchmark(
            **({**SMOKE["rf"], "n_trees": 2 * jax.device_count()}
               if smoke else {})),
        # PR 16: the histogram-formulation A/B the profile pass priced —
        # dense one-hot MXU (the incumbent default's mechanism) vs the
        # 25 GB/s scatter wall; counts are bit-identical int32, so
        # train_acc gates only against harness drift
        "rf_dense_hist": lambda: rf.benchmark(
            hist_algo="dense",
            **({**SMOKE["rf_dense_hist"], "n_trees": 2 * jax.device_count()}
               if smoke else {})),
        "rf_scatter_hist": lambda: rf.benchmark(
            hist_algo="scatter",
            **({**SMOKE["rf_scatter_hist"],
                "n_trees": 2 * jax.device_count()}
               if smoke else {})),
        # PR 17: the on-chip histogram kernel arm (ops/rf_kernel.py) —
        # bit-identical counts to the dense arm (tests assert it), only
        # the memory schedule differs; CONDITIONAL on rf_dense_hist in
        # flip_decision
        "rf_hist_pallas": lambda: rf.benchmark(
            hist_algo="pallas",
            **({**SMOKE["rf_hist_pallas"],
                "n_trees": 2 * jax.device_count()}
               if smoke else {})),
        # the REAL-ingest half of the north-star (disk npy memmap through
        # fit_streaming; VERDICT r2 item 2) — full mode keeps a 12 GB
        # float16 file in .bench_data/ for reuse; the honest 100M-row run
        # is scripts/bench_ingest.py directly (60 GB, host-bound).
        # LAST deliberately: generating the file on this 1-core host took
        # 864 s of the 1200 s watchdog window on 2026-07-31 and the
        # watchdog exit then skipped every config after it — a slow
        # ingest can only cost itself here (prewarm_bench_cache.py
        # pre-generates outside any watchdog)
        "kmeans_ingest": lambda: _bench_ingest(smoke),
        "kmeans_ingest_int8": lambda: _bench_ingest(smoke,
                                                    quantize="int8"),
    }
    assert set(SPRINT_ORDER) == set(configs), (
        set(SPRINT_ORDER) ^ set(configs))  # config added to one list only
    configs = {name: configs[name] for name in SPRINT_ORDER}
    from harp_tpu.utils import chip
    from harp_tpu.utils.roofline import annotate

    device = chip.device_info()
    env = {
        "date": datetime.date.today().isoformat(),
        "backend": jax.default_backend(),
        **device,  # platform / device_kind / n_devices
        "jax": jax.__version__,
        "smoke": smoke,
        # the r2 verdict's stale-claims weakness was ATTRIBUTION: a rate
        # means little without the code it measured
        "commit": _git_commit(),
    }
    for name, fn in configs.items():
        if only and name not in only:
            continue
        if skip and name in skip:
            continue
        if watchdog is not None:
            watchdog.arm(name)  # restart the hang clock per config
        try:
            result = fn()
        except Exception as e:  # noqa: BLE001 - boundary: the other
            # configs still run; main() exits non-zero on any error row
            yield {"config": name, "error": f"{type(e).__name__}: {e}", **env}
            continue
        # % of the measuring device's peak, where modeled
        result = annotate(name, result, device["device_kind"])
        yield {"config": name,
               **{k: (round(v, 4) if isinstance(v, float) else v)
                  for k, v in result.items()}, **env}
    if watchdog is not None:
        watchdog.cancel()


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=None, help="append JSONL records here")
    p.add_argument("--smoke", action="store_true")
    # one list for --only AND --skip: a typo in either is an argparse
    # error, never a silent empty sweep or a silently-unskipped config
    # derived from SPRINT_ORDER so a config added there is immediately
    # addressable here (a hand-copied list drifted in round 5: the hot
    # LL-gate pair was briefly un-skippable)
    config_names = sorted(SPRINT_ORDER)
    p.add_argument("--only", nargs="+", default=None, metavar="CONFIG",
                   choices=config_names,
                   help="subset of configs to run (typo → argparse error, "
                        "not a silent empty sweep)")
    p.add_argument("--skip", nargs="+", default=None, metavar="CONFIG",
                   choices=config_names,
                   help="configs to exclude (the measurement run skips the "
                        "pallas configs when kernel_equiv_check.py fails "
                        "on silicon — ADVICE r3: no pallas row may be "
                        "recorded before the equivalence check passes; a "
                        "typo'd skip must error, not silently record an "
                        "unverified row)")
    # PR 13: perfmodel sprint pruning — the model's candidate ranking
    # mapped onto the --only machinery; gate partners are ALWAYS pulled
    # in (gate_closure), so a pruned sprint can still produce verdicts
    p.add_argument("--predicted-top", type=int, default=None, metavar="N",
                   help="run only the perfmodel's top-N predicted flip "
                        "candidates (plus their JOINT/EXCLUSIVE "
                        "partners and CONDITIONAL anchors — "
                        "flip_decision's gates stay authoritative); "
                        "mutually exclusive with --only")
    p.add_argument("--topology",
                   choices=("auto", "single_chip", "sim_ring_8", "v4_32"),
                   default="v4_32",
                   help="topology the --predicted-top ranking prices "
                        "wire terms against (default: the north-star "
                        "v4_32 slice)")
    p.add_argument("--dry-run", action="store_true",
                   help="print the selected config list and exit "
                        "without benchmarking anything (CPU-only; the "
                        "drive_check/CI hook for --predicted-top)")
    args = p.parse_args(argv)
    if args.predicted_top is not None:
        if args.only:
            p.error("--predicted-top computes its own --only list; "
                    "pass one or the other")
        only, ranked, unpriced = predicted_only(args.predicted_top,
                                                args.topology)
        print(json.dumps({"predicted_top": args.predicted_top,
                          "topology": args.topology,
                          "ranking": ranked, "unpriced": unpriced,
                          "only": only}), file=sys.stderr, flush=True)
        args.only = only
    if args.dry_run:
        sel = [c for c in SPRINT_ORDER
               if (not args.only or c in args.only)
               and not (args.skip and c in args.skip)]
        print(json.dumps({"dry_run": True, "would_run": sel}))
        return 0
    from harp_tpu.utils import chip

    chip.setup_compile_cache()
    if not args.smoke:
        chip.require_tpu("measure_all.py")  # full shapes measure: TPU only

    sink = open(args.out, "a") if args.out else None
    failed = []

    def emit(rec):
        if "error" in rec:
            failed.append(rec["config"])
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    # A hung device call is uninterruptible from Python, so recovery
    # within the process is impossible: the watchdog names the hung config
    # in a final error record (prior records are already flushed) and exits.
    from harp_tpu.utils.timing import HangWatchdog

    watchdog = HangWatchdog(
        on_fire=lambda what: emit(
            {"config": what,
             "error": f"hang: no result after {watchdog.timeout_s:.0f}s"}))
    watchdog.arm("backend init")
    try:
        for rec in run_all(args.smoke, args.only, watchdog, args.skip):
            emit(rec)
    finally:
        watchdog.cancel()
        if sink:
            sink.close()
    if failed:
        print(f"measure_all.py: {len(failed)} config(s) failed: "
              f"{' '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
