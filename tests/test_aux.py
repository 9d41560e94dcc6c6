"""Aux subsystem tests: mapper lifecycle, metrics, checkpoint, config, profiler."""

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.mapper import CollectiveApp, run_app
from harp_tpu.utils.checkpoint import CheckpointManager
from harp_tpu.utils.config import parse_into
from harp_tpu.utils.metrics import MetricsLogger


def test_collective_app_lifecycle(mesh, tmp_path):
    path = str(tmp_path / "metrics.jsonl")

    class MiniKMeans(CollectiveApp):
        def map_collective(self):
            from harp_tpu.models.kmeans import fit

            pts = np.random.default_rng(0).normal(size=(64, 4)).astype(np.float32)
            c, inertia = fit(pts, k=2, iters=2, mesh=self.mesh, seed=None)
            self.metrics.log(step=1, inertia=inertia)
            return c

    c = run_app(MiniKMeans, config={"k": 2}, mesh=mesh, metrics_path=path)
    assert c.shape == (2, 4)
    recs = [json.loads(l) for l in open(path)]
    assert recs and "inertia" in recs[0] and recs[0]["step"] == 1


def test_keyval_reader(mesh, tmp_path):
    """KeyValReader hands this worker its whole-file splits (L4 parity)."""
    from harp_tpu.mapper import KeyValReader

    paths = []
    for i in range(3):
        p = tmp_path / f"part{i}.csv"
        p.write_text("\n".join(f"{i}.0,{j}.0" for j in range(4)))
        paths.append(str(p))

    class App(CollectiveApp):
        def map_collective(self):
            return {k: v for k, v in self.reader}

    app = App(mesh=mesh, input_paths=paths)
    assert isinstance(app.reader, KeyValReader)
    assert sorted(app.reader.paths) == sorted(paths)
    data = app.run()
    assert len(data) == 3
    assert data[paths[0]].shape == (4, 2)

    # imperative Harp-style API
    r = KeyValReader(paths[:1])
    with pytest.raises(RuntimeError, match="next_key_value"):
        r.current_key()  # before the first advance
    assert r.next_key_value()
    assert r.current_key() == paths[0]
    v = r.current_value()
    assert v.shape == (4, 2)
    assert r.current_value() is v  # cached per position, not re-parsed
    assert not r.next_key_value()


def test_example_kmeans_app_runs():
    """The MIGRATING.md example app runs end-to-end on the CPU sim."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "kmeans_app.py"),
         "--n", "512", "--d", "4", "--k", "2", "--iters", "2"],
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "centroid_norm" in out.stdout


def test_metrics_logger_without_file():
    m = MetricsLogger()
    rec = m.log(step=3, loss=1.5)
    assert rec["loss"] == 1.5 and rec["step"] == 3
    m.close()


def test_metrics_logger_context_manager_closes_idempotently(tmp_path):
    import json

    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(path) as m:
        m.log(step=0, loss=2.0)
        m.close()  # explicit close inside the with: __exit__ must tolerate
    assert m._fh is None
    m.close()  # and again after exit
    rows = [json.loads(ln) for ln in open(path)]
    assert rows[0]["loss"] == 2.0 and rows[0]["step"] == 0


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    assert mgr.latest_step() is None
    state = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
             "step_count": np.int32(7)}
    for s in (1, 5, 9):
        mgr.save(s, state)
    assert mgr.steps() == [5, 9]  # keep=2 pruned step 1
    step, restored = mgr.restore()
    assert step == 9
    np.testing.assert_array_equal(restored["w"], state["w"])


def test_checkpoint_restore_missing(tmp_path):
    with pytest.raises(FileNotFoundError):
        CheckpointManager(str(tmp_path / "empty")).restore()


def test_checkpoint_restore_latest(tmp_path):
    """The serve load path: newest step without the caller enumerating
    steps; empty root fails loudly like restore()."""
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    for s in (2, 11, 7):
        mgr.save(s, {"v": np.float32(s)})
    step, state = mgr.restore_latest()
    assert step == 11 and float(state["v"]) == 11.0
    with pytest.raises(FileNotFoundError):
        CheckpointManager(str(tmp_path / "none")).restore_latest()


def test_parse_into():
    @dataclasses.dataclass
    class Cfg:
        k: int = 100
        lr: float = 0.1
        name: str = "x"
        verbose: bool = False

    cfg = parse_into(Cfg, ["--k", "7", "--lr", "0.5", "--verbose"])
    assert cfg == Cfg(k=7, lr=0.5, name="x", verbose=True)
    cfg = parse_into(Cfg, [], k=9)  # programmatic default override
    assert cfg.k == 9


def test_resume_flow(mesh, tmp_path):
    """The --resume pattern: train, checkpoint, restore, continue."""
    from harp_tpu.models.mlp import MLPConfig, MLPTrainer, synthetic_mnist

    mgr = CheckpointManager(str(tmp_path / "run"))
    cfg = MLPConfig(sizes=(8, 16, 2))
    x, y = synthetic_mnist(n=64, d=8, classes=2, seed=0)
    tr = MLPTrainer(cfg, mesh, seed=0)
    tr.train_batch(x, y)
    mgr.save(1, {"params": tr.params})

    tr2 = MLPTrainer(cfg, mesh, seed=1)  # different init
    step, state = mgr.restore()
    tr2.params = state["params"]
    for a, b in zip(np.asarray(tr.params[0]["w"]).ravel(),
                    np.asarray(tr2.params[0]["w"]).ravel()):
        assert a == b
    tr2.train_batch(x, y)  # continues without error


def test_parse_into_tuple_field():
    @dataclasses.dataclass
    class Cfg:
        sizes: tuple = (8, 16, 2)

    cfg = parse_into(Cfg, ["--sizes", "4,8"])
    assert cfg.sizes == (4, 8)
    assert parse_into(Cfg, []).sizes == (8, 16, 2)


def test_hang_watchdog_fires_with_record_and_exit():
    import time

    from harp_tpu.utils.timing import HangWatchdog

    fired, exits = [], []
    wd = HangWatchdog(timeout_s=0.05, on_fire=fired.append,
                      _exit=exits.append)
    wd.arm("lda")
    time.sleep(0.4)
    assert fired == ["lda"] and exits == [3]


def test_hang_watchdog_cancel_and_rearm():
    import time

    from harp_tpu.utils.timing import HangWatchdog

    fired = []
    wd = HangWatchdog(timeout_s=0.05, on_fire=fired.append, _exit=lambda c: None)
    wd.arm("a")
    wd.arm("b")   # re-arm replaces the pending timer
    wd.cancel()   # cancel before expiry: nothing fires
    time.sleep(0.2)
    assert fired == []
    wd.arm("c")
    time.sleep(0.2)
    assert fired == ["c"]


def test_hang_watchdog_stale_fire_is_noop():
    """A timer that left the waiting stage right as cancel()/arm() ran must
    not emit a hang record for a config that actually finished."""
    from harp_tpu.utils.timing import HangWatchdog

    fired, exits = [], []
    wd = HangWatchdog(timeout_s=60, on_fire=fired.append, _exit=exits.append)
    wd.arm("a")
    stale_gen = wd._gen
    wd.cancel()               # config "a" finished in time
    wd._fire("a", stale_gen)  # the race: _fire already dispatched
    assert fired == [] and exits == []
    wd._fire("a", wd._gen)    # current generation still fires
    assert fired == ["a"] and exits == [3]


def test_example_mfsgd_app_runs():
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "mfsgd_app.py"),
         "--users", "64", "--items", "48", "--nnz", "600",
         "--rank", "4", "--epochs", "4"],
        capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-800:]
    assert "rmse_final" in out.stdout


def test_example_longctx_layer_runs():
    """The long-context stack example (RoPE + windowed GQA ring attention +
    DP allreduce) trains and its loss descends."""
    import ast
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "longctx_layer.py"),
         "--seq", "128", "--steps", "12", "--window", "24"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-800:]
    rec = ast.literal_eval(out.stdout.strip().splitlines()[-1])
    assert rec["loss_final"] < rec["loss_first"]


def test_example_pipeline_moe_app_runs():
    """The PP+EP composition example: GPipe loss descends over the
    stage ring; the MoE dispatch matches the dense reference."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable,
         os.path.join(root, "examples", "pipeline_moe_app.py"),
         "--steps", "8"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-800:]
    assert "pipeline[8 stages" in out.stdout
    assert "== dense reference" in out.stdout


def test_profiling_op_breakdown(mesh, tmp_path):
    """trace() + op_breakdown: capture a jitted run, get a per-op table."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.utils.profiling import op_breakdown, trace

    x = jnp.ones((256, 256))
    f = jax.jit(lambda a: (a @ a).sum())
    float(f(x))  # compile outside the trace
    with trace(str(tmp_path / "tr")) as d:
        float(f(x))
    rows = op_breakdown(d, top=5)
    assert rows and all(isinstance(n, str) and s >= 0 for n, s in rows)

    # a second capture into the SAME dir: totals must come from the newest
    # session only, not the sum of both (reused default logdirs double)
    import time

    time.sleep(1.1)  # session dirs are timestamped at second granularity
    with trace(d):
        float(f(x))
    rows2 = op_breakdown(d, top=5)
    # newest-session-only, asserted structurally (device-op durations vary
    # run to run, so a wall-clock ratio between captures would flake):
    # the logdir parse must equal a parse of the newest session dir alone
    import glob

    sessions = sorted(glob.glob(f"{d}/plugins/profile/*/"))
    assert len(sessions) == 2, sessions
    assert rows2 == op_breakdown(sessions[-1], top=5)

    with pytest.raises(FileNotFoundError, match="trace.json.gz"):
        op_breakdown(str(tmp_path / "nope"))


def test_op_breakdown_self_time_unnests_parent_spans(tmp_path):
    """TPU device tracks nest (jit module ⊃ while ⊃ fusions); the table
    must charge parents only their uncovered time or shares triple-count
    (the 2026-07-31 kmeans capture read jit_run at 28% this way)."""
    import gzip
    import json

    from harp_tpu.utils.profiling import op_breakdown

    #            0         10        20        30        40
    # jit_run    [----------------------------------------]   40 us
    #   while.1      [------------------]                      20 us
    #     fusion.1     [------]  [------]                      8+8 us
    #   fusion.2                              [------]         8 us
    events = [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 7, "tid": 1, "name": "jit_run", "ts": 0,
         "dur": 40},
        {"ph": "X", "pid": 7, "tid": 1, "name": "while.1", "ts": 4,
         "dur": 20},
        {"ph": "X", "pid": 7, "tid": 1, "name": "fusion.1", "ts": 5,
         "dur": 8},
        {"ph": "X", "pid": 7, "tid": 1, "name": "fusion.1", "ts": 14,
         "dur": 8},
        {"ph": "X", "pid": 7, "tid": 1, "name": "fusion.2", "ts": 30,
         "dur": 8},
        # host-track span must stay filtered out
        {"ph": "X", "pid": 1, "tid": 1, "name": "host_thing", "ts": 0,
         "dur": 999},
    ]
    d = tmp_path / "fake"
    d.mkdir()
    with gzip.open(d / "x.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)

    got = dict(op_breakdown(str(d)))
    assert "host_thing" not in got
    assert abs(got["fusion.1"] - 16e-6) < 1e-12
    assert abs(got["fusion.2"] - 8e-6) < 1e-12
    assert abs(got["while.1"] - 4e-6) < 1e-12   # 20 − 16 covered
    assert abs(got["jit_run"] - 12e-6) < 1e-12  # 40 − 20 − 8 covered
    assert abs(sum(got.values()) - 40e-6) < 1e-12  # shares sum to wall

    raw = dict(op_breakdown(str(d), self_time=False))
    assert abs(raw["jit_run"] - 40e-6) < 1e-12  # old behavior, opt-in
