"""The plain reference of the MLP cell (``perf/reference/mlp.py``) against
numpy written out longhand, and ``MLPTrainer`` against the reference:
step for step in the program's own batch order, the data-parallel
deployment against the global batch, ``load_resident`` given device
arrays, and the spans and the step counter the cell's metrics read."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from harp_tpu.models import mlp as M
from harp_tpu.parallel.mesh import WorkerMesh
from harp_tpu.utils import flightrec, telemetry
from perf.reference import mlp as reference

SIZES = (16, 32, 24, 4)


def _params(rng, sizes=SIZES):
    return [{"w": rng.standard_normal((fi, fo)).astype(np.float32) * 0.3,
             "b": rng.standard_normal(fo).astype(np.float32) * 0.1}
            for fi, fo in zip(sizes[:-1], sizes[1:])]


def _numpy_loss_and_grads(params, x, y):
    """float64, one row at a time: forward, softmax cross-entropy and
    backpropagation as the textbook has them."""
    p = [{k: np.asarray(v, np.float64) for k, v in layer.items()}
         for layer in params]
    grads = [{k: np.zeros_like(v) for k, v in layer.items()} for layer in p]
    loss = 0.0
    for row, label in zip(np.asarray(x, np.float64), y):
        acts, h = [], row
        for layer in p[:-1]:
            acts.append(h)
            h = np.maximum(h @ layer["w"] + layer["b"], 0.0)
        acts.append(h)
        z = h @ p[-1]["w"] + p[-1]["b"]
        prob = np.exp(z - z.max())
        prob /= prob.sum()
        loss -= np.log(prob[label])
        delta = prob.copy()
        delta[label] -= 1.0
        for i in range(len(p) - 1, -1, -1):
            grads[i]["w"] += np.outer(acts[i], delta)
            grads[i]["b"] += delta
            delta = (p[i]["w"] @ delta) * (acts[i] > 0)
    n = len(y)
    return loss / n, [{k: v / n for k, v in g.items()} for g in grads]


def _leaves_close(got, want, rtol, atol=0.0):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol,
                                   atol=atol)


def test_reference_is_the_textbook_forward_loss_and_gradients():
    rng = np.random.default_rng(0)
    params = _params(rng)
    x = rng.standard_normal((40, SIZES[0])).astype(np.float32)
    y = rng.integers(0, SIZES[-1], 40).astype(np.int32)
    want_loss, want = _numpy_loss_and_grads(params, x, y)
    loss, grads = reference.loss_and_grads(params, x, y)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    _leaves_close(grads, want, rtol=2e-4, atol=1e-6)
    # every leaf, the biases among them, gets a gradient
    assert all(np.abs(np.asarray(g)).max() > 0 for g in jax.tree.leaves(grads))
    # the forward alone, longhand
    h = x.astype(np.float64)
    for layer in params[:-1]:
        h = np.maximum(h @ layer["w"] + layer["b"], 0.0)
    np.testing.assert_allclose(
        reference.logits(params, x), h @ params[-1]["w"] + params[-1]["b"],
        rtol=1e-5, atol=1e-5)
    # and autodiff of the plain forward agrees with the longhand gradients
    auto = jax.grad(lambda p: reference.loss_and_grads(p, x, y)[0])(params)
    _leaves_close(grads, auto, rtol=1e-4, atol=1e-6)


def test_reference_as_stated_is_the_same_arithmetic_at_default_dots():
    """``precision=AS_STATED`` changes the dots' precision and nothing
    else: every dot of the lowered forward and backward carries it, and
    where a float32 dot is exact (the CPU) the two readings are one."""
    rng = np.random.default_rng(1)
    params = _params(rng)
    x = rng.standard_normal((40, SIZES[0])).astype(np.float32)
    y = rng.integers(0, SIZES[-1], 40).astype(np.int32)
    for precision, word in ((reference.HI, "HIGHEST"),
                            (reference.AS_STATED, "DEFAULT")):
        text = reference.loss_and_grads.lower(
            params, x, y, precision=precision).as_text()
        dots = [line for line in text.splitlines() if "dot_general" in line]
        # three forward, three weight gradients, two input gradients
        assert len(dots) == 8
        assert all(f"precision = [{word}, {word}]" in line for line in dots)
    _leaves_close(
        reference.loss_and_grads(params, x, y, precision=reference.AS_STATED),
        reference.loss_and_grads(params, x, y), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(
        reference.logits(params, x, precision=reference.AS_STATED),
        reference.logits(params, x), rtol=1e-6, atol=1e-6)


def test_reference_sgd_is_one_step_after_another_in_the_order_given():
    rng = np.random.default_rng(1)
    params = _params(rng)
    n, b, lr = 96, 8, 0.05
    x = rng.standard_normal((n, SIZES[0])).astype(np.float32)
    y = rng.integers(0, SIZES[-1], n).astype(np.int32)
    order = np.array([3, 0, 7, 7, 11], np.int32)
    got, losses = reference.sgd(params, x, y, order, lr, batch_per_worker=b)
    want = params
    for i in order:
        rows = slice(i * b, (i + 1) * b)
        _, g = _numpy_loss_and_grads(want, x[rows], y[rows])
        want = [{k: layer[k] - lr * gl[k] for k in layer}
                for layer, gl in zip(want, g)]
    _leaves_close(got, want, rtol=1e-4, atol=1e-6)
    assert losses.shape == (5,)
    # two bands of rows: batch i is rows [i b, (i + 1) b) of BOTH bands
    got2, _ = reference.sgd(params, x, y, order[:1], lr,
                            batch_per_worker=b, workers=2)
    rows = np.r_[3 * b:4 * b, n // 2 + 3 * b:n // 2 + 4 * b]
    _, g = _numpy_loss_and_grads(params, x[rows], y[rows])
    _leaves_close(got2, [{k: layer[k] - lr * gl[k] for k in layer}
                         for layer, gl in zip(params, g)],
                  rtol=1e-4, atol=1e-6)
    # the blocked whole-table loss is the mean over every row
    table = [(jnp.asarray(x[:48]), jnp.asarray(y[:48])),
             (jnp.asarray(x[48:]), jnp.asarray(y[48:]))]
    assert reference.table_loss(params, table) == pytest.approx(
        _numpy_loss_and_grads(params, x, y)[0], rel=1e-5)


def test_reference_keeps_to_itself():
    src = open(reference.__file__).read()
    assert "harp_tpu" not in src.replace("``harp_tpu``", "")
    assert "Precision.HIGHEST" in src and "optax" not in src


def _task(n, seed=3):
    return M.synthetic_mnist(n=n, d=SIZES[0], classes=SIZES[-1], seed=seed)


@pytest.mark.parametrize("workers", [1, 4])
def test_trainer_follows_the_reference_step_for_step(workers):
    """20 batches in the program's own order, ``sgd``: the parameters
    after ``fit_resident`` are the reference's after plain SGD over the
    batches ``resident_batch_order`` names.  On four workers the
    averaged gradient is the reference's gradient of the global batch
    (rows ``[i b, (i + 1) b)`` of every worker's band together): what
    ties the cell's one-chip share to the deployment."""
    mesh = WorkerMesh(jax.devices()[:workers])
    cfg = M.MLPConfig(sizes=SIZES, lr=0.05)
    bpw, nb = 8, 10
    x, y = _task(workers * bpw * nb)
    tr = M.MLPTrainer(cfg, mesh, seed=2)
    initial = tr.params
    assert tr.load_resident(x, y, batch_size=workers * bpw) == len(x)
    order = tr.resident_batch_order(epochs=2, seed=9)
    assert order.shape == (2, nb)
    assert all(sorted(o) == list(range(nb)) for o in order)
    assert not (order[0] == order[1]).all()  # reshuffled every epoch
    hist = tr.fit_resident(epochs=2, seed=9)
    want, losses = reference.sgd(initial, x, y, order.reshape(-1), cfg.lr,
                                 batch_per_worker=bpw, workers=workers)
    _leaves_close(tr.params, want, rtol=2e-4, atol=2e-6)
    # the loss each epoch reports is its last batch's
    assert [h[0] for h in hist] == pytest.approx(
        [float(losses[nb - 1]), float(losses[-1])], rel=1e-4)
    assert tr.steps_run == 2 * nb
    # the next call visits another order, and says so beforehand
    nxt = tr.resident_batch_order(epochs=1, seed=9)
    assert not (nxt[0] == order[0]).all()


def test_load_resident_takes_device_arrays_as_they_are(mesh):
    """Given arrays already row-sharded over its mesh the trainer stages
    nothing through the host (no H2D at all when nothing is trimmed, the
    kept indices alone when rows are) and trains bit for bit as given the
    same host arrays."""
    cfg = M.MLPConfig(sizes=SIZES, lr=0.05)
    x, y = _task(200)
    for n, trimmed in ((192, 0), (200, 8)):
        host, dev = (M.MLPTrainer(cfg, mesh, seed=0) for _ in range(2))
        assert host.load_resident(x[:n], y[:n], batch_size=64, seed=5) == 192
        xd, yd = mesh.shard_array(x[:n], 0), mesh.shard_array(y[:n], 0)
        h2d = []
        with flightrec.observe_h2d(lambda nbytes, site: h2d.append(nbytes)):
            assert dev.load_resident(xd, yd, batch_size=64, seed=5) == 192
        assert h2d == ([] if not trimmed else [192 * 4])
        if not trimmed:
            assert dev._resident[0] is xd and dev._resident[1] is yd
        for a, b in zip(host._resident[:2], dev._resident[:2]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
        assert host.fit_resident(3, seed=1) == dev.fit_resident(3, seed=1)
        for a, b in zip(jax.tree.leaves(host.params),
                        jax.tree.leaves(dev.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a device array of another dtype is cast there too
    dev = M.MLPTrainer(cfg, mesh, seed=0)
    dev.load_resident(mesh.shard_array(x[:192].astype(np.float16), 0),
                      mesh.shard_array(y[:192].astype(np.int8), 0),
                      batch_size=64)
    assert dev._resident[0].dtype == jnp.float32
    assert dev._resident[1].dtype == jnp.int32
    # an array that lies elsewhere goes the way host arrays go
    dev.load_resident(jnp.asarray(x[:192]), jnp.asarray(y[:192]),
                      batch_size=64)
    np.testing.assert_array_equal(np.asarray(dev._resident[0]), x[:192])


def test_resident_spans_counter_and_ledger(mesh):
    cfg = M.MLPConfig(sizes=SIZES, lr=0.05)
    x, y = _task(200)
    tr = M.MLPTrainer(cfg, mesh, seed=0)
    seen = {"dispatch": [], "readback": 0}

    def on_readback(_):
        seen["readback"] += 1

    with telemetry.scope():
        tr.load_resident(x, y, batch_size=64, seed=5)
        with flightrec.observe_dispatches(seen["dispatch"].append), \
                flightrec.observe_readbacks(on_readback):
            tr.fit_resident(epochs=2)
            tr.fit_resident(epochs=2)
        spans = {r["path"]: r for r in telemetry.tracer.records}
        comm = telemetry.ledger.summary()["mlp.epochs"]
    load = spans["mlp.load_resident"]
    assert (load["rows"], load["trimmed"]) == (192, 8)
    assert load["bytes"] == 192 * (SIZES[0] * 4 + 4)
    # the host path's placement stays its child
    assert spans["mlp.load_resident/mesh.shard_array"]["depth"] == 1
    assert spans["mlp.epochs"]["epochs"] == 2
    # one dispatch and one readback a call, the program found again
    assert seen == {"dispatch": ["mlp.epochs"] * 2, "readback": 2}
    assert len(tr._epoch_fns) == 1
    # the gradient allreduce priced per optimizer step: every parameter
    # and the two metrics, float32, 2 epochs x 3 batches x 2 calls
    assert comm["executions"] == tr.steps_run == 12
    assert comm["bytes_per_execution"] == 4 * (M.param_count(cfg) + 2)
    # nothing is recorded with telemetry off
    telemetry.tracer.reset()
    telemetry.ledger.reset()
    tr.load_resident(x, y, batch_size=64, seed=5)
    tr.fit_resident(epochs=2)
    assert telemetry.tracer.records == []
    assert telemetry.ledger.summary() == {}
    assert tr.steps_run == 18
    tr.train_batch(x[:64], y[:64])
    tr.fit(x, y, batch_size=64, epochs=1)
    assert tr.steps_run == 18 + 1 + 3
