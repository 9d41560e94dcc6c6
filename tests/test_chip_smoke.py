"""chip_smoke.py off the chip: its phases by import, at toy shapes, on the
8-device CPU simulation — and the ways it must refuse to pass.

The full-width run is the chip's (``python chip_smoke.py`` through the
chip tool); its full-width COMPILE is tests/test_chip_compile.py.  Here:
the phase code is exercised end to end (kernels interpreted), the script
exits non-zero on a non-TPU backend without running a phase, a phase that
raises, mismatches or reports a non-finite value ends the run non-zero,
and the compile cache goes where the contract says.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from harp_tpu.utils import chip

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY_KMEANS = dict(n=8192, d=32, k=16, on_tpu=False)
TOY_MFSGD = dict(n_users=512, n_items=256, nnz=20_000, rank=8, epochs=3,
                 u_tile=8, i_tile=8, entry_cap=64)


def test_phases_pass_at_toy_shapes(mesh, capsys):
    meter = chip_smoke.Meter()
    rows = chip_smoke.run([
        ("kmeans", lambda: chip_smoke.phase_kmeans(mesh, meter, iters=10,
                                                   **TOY_KMEANS)),
        ("kmeans_fit", lambda: chip_smoke.phase_kmeans_fit(
            mesh, meter, **TOY_KMEANS)),
        ("mfsgd", lambda: chip_smoke.phase_mfsgd(mesh, meter, on_tpu=False,
                                                 **TOY_MFSGD)),
        ("verbs", lambda: chip_smoke.phase_verbs(mesh)),
    ], meter)
    by = {r["phase"]: r["result"] for r in rows}
    # each arm reports what actually ran; off the chip nothing is Mosaic
    km = by["kmeans"]
    assert [km[a]["arm"] for a in ("f32_xla", "int8_fused", "int8_xla")] \
        == ["xla_f32", "pallas_int8", "xla_int8"]
    assert by["mfsgd"]["pallas"]["rmse_final"] \
        < by["mfsgd"]["pallas"]["rmse_first_epoch"]
    assert by["verbs"]["num_workers"] == 8
    assert by["verbs"]["verbs"][-1] == "barrier"
    # the meter saw the tracked programs and their compiles
    assert {"kmeans.benchmark", "kmeans.fit", "mfsgd.epoch",
            "mfsgd.epochs"} <= {label for label, _ in meter.programs}
    assert meter.compiles > 0 and meter.compile_s > 0
    # one JSON line per phase, compile seconds apart from wall seconds
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["phase"] for ln in lines] == list(by)
    assert all({"wall_s", "compile_s", "cache_hits"} <= ln.keys()
               for ln in lines)


def test_every_registered_kernel_has_a_check_and_passes_interpreted():
    from harp_tpu.ops.kernel_registry import KERNELS

    assert set(chip_smoke.KERNEL_CHECKS) == set(KERNELS)
    out = chip_smoke.phase_kernels(chip_smoke.Meter(), on_tpu=False)
    assert set(out) == set(KERNELS)
    assert all(v["verdict"] == "ok" for v in out.values())


def test_kernel_without_a_check_fails_the_phase(monkeypatch):
    monkeypatch.delitem(chip_smoke.KERNEL_CHECKS, "rf.hist_bins")
    with pytest.raises(AssertionError, match="rf.hist_bins"):
        chip_smoke.phase_kernels(chip_smoke.Meter(), on_tpu=False)


def test_pallas_phase_needs_a_mosaic_call_on_the_chip(mesh):
    # on_tpu=True on the CPU: the kernel runs interpreted, so the lowered
    # program holds no tpu_custom_call — exactly the leak (interpret mode,
    # or an XLA fallback) the smoke must not let pass for the kernel
    meter = chip_smoke.Meter()
    with meter.watching(), pytest.raises(AssertionError,
                                         match="tpu_custom_call"):
        chip_smoke.phase_kmeans(mesh, meter, iters=2,
                                **{**TOY_KMEANS, "on_tpu": True})


def test_main_refuses_off_the_chip_without_running_a_phase(capsys):
    with pytest.raises(SystemExit) as ei:
        chip_smoke.main()
    assert ei.value.code == 1
    out, err = capsys.readouterr()
    assert out == ""  # no phase line, no result line
    assert "needs a TPU" in err


def test_script_exits_nonzero_off_the_chip():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""


def _boom():
    raise RuntimeError("synthetic phase failure")


def _mismatch():
    np.testing.assert_allclose(1.0, 1.1, rtol=1e-5)


@pytest.mark.parametrize("thunk, exc", [
    (_boom, RuntimeError),                       # raises
    (_mismatch, AssertionError),                 # disagrees with reference
    (lambda: {"inertia": float("nan")}, AssertionError),       # non-finite
    (lambda: {"a": {"b": [1.0, float("inf")]}}, AssertionError),
])
def test_a_failing_phase_is_never_downgraded(thunk, exc, capsys):
    ran = []
    with pytest.raises(exc):
        chip_smoke.run([("bad", thunk),
                        ("after", lambda: ran.append(1) or {})],
                       chip_smoke.Meter())
    assert not ran  # the run ends there
    assert capsys.readouterr().out == ""  # and reports nothing as passed


def test_a_failing_phase_gives_a_nonzero_exit():
    code = ("import chip_smoke\n"
            "chip_smoke.run([('bad', lambda: {'x': float('nan')})], "
            "chip_smoke.Meter())\n"
            "print('{\"ok\": true}')\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_obeys_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    # set: JAX reads the variable itself, the helper touches nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert chip.setup_compile_cache() == str(tmp_path / "c")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "c").exists()
    # unset: a fixed path in the checkout, never a temp name, pid or time
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert chip.setup_compile_cache() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir \
            == os.path.join(ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_no_other_code_sets_a_compile_cache():
    # one function places the cache; nothing else may name the option
    offenders = []
    for top in ("harp_tpu", "scripts", "examples"):
        for dirpath, _, files in os.walk(os.path.join(ROOT, top)):
            offenders += [os.path.join(dirpath, f) for f in files
                          if f.endswith(".py")]
    offenders += [os.path.join(ROOT, f) for f in
                  ("chip_smoke.py", "__graft_entry__.py")]

    def sets_cache(path):
        with open(path) as f:
            text = f.read()
        return "jax_compilation_cache_dir" in text or "set_cache_dir" in text

    hits = [p for p in offenders if sets_cache(p)]
    assert hits == [os.path.join(ROOT, "harp_tpu", "utils", "chip.py")]


def test_serve_bench_aot_cache_lives_under_the_same_root(monkeypatch,
                                                         tmp_path):
    from harp_tpu.serve import bench as serve_bench

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve_bench._default_cache_dir() \
        == os.path.join(str(tmp_path), "serve_aot")
