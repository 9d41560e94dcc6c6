"""Two real processes form a mesh via jax.distributed — the multi-host path.

The reference's analogue is pseudo-distributed Hadoop: real sockets over
loopback (SURVEY.md §5).  Ours is two OS processes joined by
``jax.distributed.initialize``, with collectives crossing the boundary over
Gloo (the CPU stand-in for DCN) — no mocks anywhere.
"""

import os
import socket
import subprocess
import sys


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


import pytest


def _run_workers(n_procs: int, local_devices: int = 1,
                 timeout: int = 360) -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    script = os.path.join(here, "multiproc_worker.py")
    port = str(_free_port())
    # strip the harness's XLA_FLAGS: conftest forces 8 CPU devices per
    # process; the worker sets its own per-process device count
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen([sys.executable, script, str(i), port,
                          str(n_procs), str(local_devices)],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True, env=env)
        for i in range(n_procs)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
        assert "MULTIPROC OK" in out


@pytest.mark.parametrize("n_procs", [2, 4])
def test_multi_process_distributed(n_procs):
    """Every collective family crosses a REAL process boundary (see
    multiproc_worker.py), at 2 and at 4 processes — ring direction,
    all_to_all block layout and bucket routing all degenerate at 2."""
    _run_workers(n_procs)


def test_pod_shaped_topology():
    """The v4-32 shape (VERDICT r2 item 6): 2 processes × 4 simulated
    devices each, ONE 8-worker mesh spanning both — intra-process (ICI
    stand-in) and inter-process (Gloo/DCN stand-in) links coexist, and
    every check validates all 4 local shards per process against the
    global expectation, so a layout that is only right at one device per
    process cannot pass."""
    _run_workers(2, local_devices=4)
