#!/usr/bin/env python3
"""One run of one cell:

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; the last line of standard output is the result object and
nothing else.  Refuses anything but a TPU with the chips the cell asks
for.  See ``perf/README.md``.
"""

import os
import time


def _process_start() -> float:
    """``time.perf_counter()`` as it stood when this process was made
    (interpreter start-up belongs to set-up too)."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            up = float(fh.read().split()[0])
        return now - max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return now


_START = _process_start()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perf import harness

    result = harness.run_cell(root, args.workload, args.seed, args.seconds,
                              bool(args.trace), start_clock=_START)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
