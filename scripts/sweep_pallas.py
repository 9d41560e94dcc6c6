#!/usr/bin/env python
"""Tile-size sweep for the fused Pallas kernels.

The dense-algo tiling was tuned on TPU (512×512 best, see
MFSGDConfig.u_tile); the fused kernels change the cost model (one-hots
never leave VMEM), so their best tiles may differ.  Sweeps
algo="pallas" over tile sizes for MF-SGD and LDA at the graded shapes,
one JSON line each (each point is a full-scale benchmark, minutes of
host prep).  On the chip, or ``--smoke`` under ``JAX_PLATFORMS=cpu``.

Usage: python scripts/sweep_pallas.py [--model mfsgd lda] [--smoke]
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))  # bench_common


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", nargs="+", default=["mfsgd", "lda"],
                   choices=["mfsgd", "lda"])
    p.add_argument("--tiles", nargs="+", type=int, default=[256, 512, 1024])
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--out", default="SWEEP_pallas.jsonl")
    args = p.parse_args(argv)

    from harp_tpu.utils import chip
    from harp_tpu.utils.timing import HangWatchdog

    chip.setup_compile_cache()
    if not args.smoke:
        chip.require_tpu("sweep_pallas.py")

    sink = open(args.out, "a")
    watchdog = HangWatchdog(on_fire=lambda what: (
        sink.write(json.dumps({"sweep": what, "error": "hang"}) + "\n"),
        sink.flush()))
    for model in args.model:
        for t in args.tiles:
            what = f"{model} pallas {t}x{t}"
            watchdog.arm(what)
            try:
                from bench_common import SMOKE

                if model == "mfsgd":
                    from harp_tpu.models import mfsgd

                    kw = {k: v for k, v in SMOKE["mfsgd_pallas"].items()
                          if not k.endswith("_tile")} if args.smoke else {}
                    r = mfsgd.benchmark(algo="pallas", u_tile=t, i_tile=t,
                                        **kw)
                else:
                    from harp_tpu.models import lda

                    from measure_all import BENCH_DATA

                    # per-tile packs cache too (tiling is in the key), so
                    # re-running a sweep point skips the host packing
                    kw = ({k: v for k, v in SMOKE["lda_pallas"].items()
                           if not k.endswith("_tile")} if args.smoke
                          else {"pack_cache": BENCH_DATA})
                    r = lda.benchmark(algo="pallas", d_tile=t, w_tile=t,
                                      **kw)
                rec = {"sweep": what, "tile": t, **{
                    k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in r.items()}}
            except Exception as e:  # a bad tile must not kill the sweep
                rec = {"sweep": what, "tile": t,
                       "error": f"{type(e).__name__}: {e}"}
            line = json.dumps(rec)
            print(line, flush=True)
            sink.write(line + "\n")
            sink.flush()
    watchdog.cancel()
    sink.close()


if __name__ == "__main__":
    main()
