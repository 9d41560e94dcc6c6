#!/usr/bin/env python
"""Per-config XLA op-breakdown capture — the trace half of the perf story.

VERDICT r2 item 7: the roofline annotations (utils/roofline.py) are
analytic models; this script backs them with traces.  For each graded
config it runs a SHORT benchmark inside ``utils.profiling.trace``, then
records the top device ops by total time next to the benchmark dict and
its roofline fields, one JSON line per config → ``PROFILE_local.jsonl``.

Read the output asking two questions per config:
1. does the op class the roofline model says is the bound (matmul vs
   memory-bound scatter/gather) actually dominate the trace?
2. is there an op eating >10% that the model has no term for?

Runs on the chip (one process; it is the process that holds the chip
that can trace it).  ``--smoke`` under ``JAX_PLATFORMS=cpu`` checks the
plumbing only: CPU traces have no device track, so compile/host events
appear in the table (op_breakdown's device filter only engages on TPU,
where each benchmark's internal compile lands on the host track and the
op table is pure device time).
"""

import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))  # bench_common


def profiled_configs(smoke: bool):
    """Short-running variants: one trace needs seconds, not minutes."""
    from bench_common import SMOKE
    from harp_tpu.models import kmeans, lda, mfsgd, mlp, rf, subgraph

    from measure_all import BENCH_DATA

    small = {name: SMOKE[name]
             for name in ("kmeans", "mfsgd", "lda", "mlp", "subgraph", "rf")}
    full = {"kmeans": {"n": 1_000_000, "d": 300, "k": 100, "iters": 10},
            "mfsgd": {"epochs": 2},
            "lda": {"epochs": 1, "pack_cache": BENCH_DATA},
            "mlp": {"steps": 50},
            "subgraph": {},
            "rf": {}}
    mods = {"kmeans": kmeans, "mfsgd": mfsgd, "lda": lda, "mlp": mlp,
            "subgraph": subgraph, "rf": rf}
    kw = small if smoke else full
    configs = {name: (mods[name], kw[name]) for name in mods}
    # candidate variants traced next to their baselines so the op tables
    # ATTRIBUTE the wins (and answer the queued decisions: Db/W-carry,
    # exprace/rbg, fused kernels, overflow-tail formulation)
    configs["mfsgd_pallas"] = (
        mfsgd, {"algo": "pallas",
                **(SMOKE["mfsgd_pallas"] if smoke else kw["mfsgd"])})
    configs["mfsgd_carry"] = (mfsgd, {**kw["mfsgd"], "carry_w": True})
    configs["lda_fast"] = (lda, {**kw["lda"], "sampler": "exprace",
                                 "rng_impl": "rbg"})
    configs["lda_pallas"] = (
        lda, {"algo": "pallas",
              **(SMOKE["lda_pallas"] if smoke else kw["lda"])})
    configs["lda_carry"] = (lda, {**kw["lda"], "carry_db": True})
    configs["lda_pallas_carry"] = (
        lda, {"algo": "pallas", "carry_db": True,
              **(SMOKE["lda_pallas"] if smoke else kw["lda"])})
    # overflow-tail A/B on a graph whose tail carries real mass (the
    # uniform default's tail is empty — the r2-item-7 profile question
    # needs the powerlaw shape)
    pl = ({**SMOKE["subgraph"], "max_degree": 8} if smoke
          else {"max_degree": 16})
    configs["subgraph_pl"] = (subgraph, {**pl, "graph": "powerlaw"})
    configs["subgraph_onehot"] = (
        subgraph, {**pl, "graph": "powerlaw", "overflow_algo": "onehot"})
    return configs


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="PROFILE_local.jsonl")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--top", type=int, default=15)
    p.add_argument("--only", nargs="+", default=None)
    args = p.parse_args(argv)

    from harp_tpu.utils import chip
    from harp_tpu.utils.profiling import op_breakdown, trace
    from harp_tpu.utils.roofline import annotate
    from harp_tpu.utils.timing import HangWatchdog

    chip.setup_compile_cache()
    device = (chip.device_info() if args.smoke
              else chip.require_tpu("profile_configs.py"))

    sink = open(args.out, "a")
    watchdog = HangWatchdog(on_fire=lambda what: (
        sink.write(json.dumps({"config": what, "error": "hang"}) + "\n"),
        sink.flush()))
    watchdog.arm("backend init")
    for name, (mod, kw) in profiled_configs(args.smoke).items():
        if args.only and name not in args.only:
            continue
        watchdog.arm(name)
        logdir = tempfile.mkdtemp(prefix=f"harp_prof_{name}_")
        try:
            mod.benchmark(**kw)  # warmup/compile OUTSIDE the trace
            with trace(logdir):
                result = mod.benchmark(**kw)
            ops = op_breakdown(logdir, top=args.top)
        except Exception as e:
            rec = {"config": name, "error": f"{type(e).__name__}: {e}",
                   "trace_dir": logdir}
        else:
            # an empty op table (all spans filtered) is a per-config
            # error, not a sweep-aborting ZeroDivision
            traced = sum(t for _, t in ops) or 1.0
            raw = op_breakdown(logdir, top=args.top, self_time=False)
            rec = {"config": name,
                   **{k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in annotate(
                          name, result, device["device_kind"]).items()},
                   **device,
                   # keep the trace dir + the raw (non-self-time) table so
                   # the capture can be re-analyzed from disk if the
                   # self-time parse turns out wrong on device tracks
                   "trace_dir": logdir,
                   "top_ops": [{"op": o, "sec": round(t, 5),
                                "share_of_traced": round(t / traced, 3)}
                               for o, t in ops],
                   "top_ops_raw": [{"op": o, "sec": round(t, 5)}
                                   for o, t in raw]}
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()
    watchdog.cancel()
    sink.close()


if __name__ == "__main__":
    main()
