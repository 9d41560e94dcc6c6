"""MLP tests: DP-allreduce gradient equivalence + convergence."""

from unittest import mock

import jax
import numpy as np
import optax
import pytest

from harp_tpu.models import mlp as M

N = 8


def _optax_loss_fn(params, x, y, cfg):
    """``loss_fn`` as it stood before PR 37: the same forward under
    optax's cross-entropy, which gathers the label's logit."""
    logits = M.forward(params, x, cfg)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    return ce.mean(), logits


def _assert_leaves_equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_allclose(g, w, rtol=1e-6,
                                   atol=1e-6 * np.abs(w).max())


@pytest.mark.parametrize("through", ["value_and_grad", "train_step",
                                     "train_step_zero1"])
@pytest.mark.parametrize("half", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("classes", [4, 10, 1000])
def test_loss_fn_equals_optax_cross_entropy(mesh, classes, half, through):
    """The written-out loss (a select over the class columns picks the
    label's logit) is optax's: the loss and every leaf of the gradient,
    and the parameters one data-parallel step leaves, replicated
    optimizer state or ZeRO-1."""
    cfg = M.MLPConfig(sizes=(16, 32, classes), lr=0.1, half_precision=half,
                      zero1=through == "train_step_zero1")
    x, y = M.synthetic_mnist(n=64, d=16, classes=classes, seed=classes)
    assert y.min() >= 0 and y.max() < classes

    if through == "value_and_grad":
        params = M.init_params(cfg, jax.random.key(1))

        def read(loss_fn):
            (loss, logits), grads = jax.jit(jax.value_and_grad(
                lambda p: loss_fn(p, x, y, cfg), has_aux=True))(params)
            return loss, logits, grads
    else:
        def read(loss_fn):
            with mock.patch.object(M, "loss_fn", loss_fn):
                tr = M.MLPTrainer(cfg, mesh, seed=1)
                loss, acc = tr.train_batch(x, y)
            return loss, acc, tr.params

    _assert_leaves_equal(read(M.loss_fn), read(_optax_loss_fn))


def test_loss_fn_label_outside_the_classes_selects_nothing():
    """What ``loss_fn``'s docstring says of a label outside ``[0,
    classes)``: no column matches, so the row's loss is its
    log-normaliser (finite), whichever side the label lies on."""
    cfg = M.MLPConfig(sizes=(16, 32, 4))
    params = M.init_params(cfg, jax.random.key(0))
    x, _ = M.synthetic_mnist(n=2, d=16, classes=4, seed=0)
    loss, logits = M.loss_fn(params, x, np.array([-1, 4], np.int32), cfg)
    np.testing.assert_allclose(
        loss, jax.nn.logsumexp(logits, axis=-1).mean(), rtol=1e-6)


def test_dp_grads_equal_fullbatch(mesh):
    """N-worker allreduced step must equal a single-worker full-batch step."""
    cfg = M.MLPConfig(sizes=(16, 32, 4), lr=0.1)
    x, y = M.synthetic_mnist(n=64, d=16, classes=4, seed=1)

    t_multi = M.MLPTrainer(cfg, mesh, seed=0)
    l_multi, _ = t_multi.train_batch(x, y)

    from harp_tpu.parallel.mesh import WorkerMesh
    single = WorkerMesh(jax.devices()[:1])
    t_single = M.MLPTrainer(cfg, single, seed=0)
    l_single, _ = t_single.train_batch(x, y)

    assert abs(l_multi - l_single) < 1e-5
    for pm, ps in zip(jax.tree.leaves(t_multi.params), jax.tree.leaves(t_single.params)):
        np.testing.assert_allclose(np.asarray(pm), np.asarray(ps), rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("opt", ["sgd", "momentum", "adam"])
def test_training_converges(mesh, opt):
    cfg = M.MLPConfig(sizes=(32, 64, 8), lr=0.05 if opt != "adam" else 0.005,
                      optimizer=opt)
    x, y = M.synthetic_mnist(n=2048, d=32, classes=8, seed=0, noise=0.35)
    tr = M.MLPTrainer(cfg, mesh, seed=0)
    hist = tr.fit(x, y, batch_size=256, epochs=3)
    first_losses = np.mean([h[0] for h in hist[:4]])
    last_losses = np.mean([h[0] for h in hist[-4:]])
    assert last_losses < 0.6 * first_losses, (opt, first_losses, last_losses)
    assert tr.accuracy(x, y) > 0.8


def test_bf16_trains(mesh):
    cfg = M.MLPConfig(sizes=(32, 64, 8), lr=0.05, half_precision=True)
    x, y = M.synthetic_mnist(n=1024, d=32, classes=8, seed=0)
    tr = M.MLPTrainer(cfg, mesh, seed=0)
    hist = tr.fit(x, y, batch_size=256, epochs=3)
    assert hist[-1][0] < hist[0][0]
    # params stay f32 (mixed precision contract)
    assert all(p.dtype == np.float32 for p in jax.tree.leaves(
        jax.tree.map(np.asarray, tr.params)))


def test_bad_optimizer_raises(mesh):
    with pytest.raises(ValueError, match="unknown optimizer"):
        M.MLPTrainer(M.MLPConfig(optimizer="lion"), mesh)


def test_tp_matches_dp(mesh):
    """Tensor-parallel (2x4 data x model mesh) == data-parallel trainer.

    Same init seed, same full batch: the TP step's global loss/grads are
    the same math as DP's allreduce(AVG), so params must agree.
    """
    cfg = M.MLPConfig(sizes=(16, 32, 8), lr=0.05)
    x, y = M.synthetic_mnist(n=64, d=16, classes=8, seed=3)

    from harp_tpu.parallel.mesh import mesh_2d

    dp = M.MLPTrainer(cfg, mesh, seed=0)
    tp = M.TPMLPTrainer(cfg, mesh_2d(2, 4), seed=0)
    for _ in range(3):
        dp_loss, _ = dp.train_batch(x, y)
        tp_loss, _ = tp.train_batch(x, y)
    assert abs(dp_loss - tp_loss) < 1e-4
    for pl_dp, pl_tp in zip(dp.params, tp.params):
        np.testing.assert_allclose(np.asarray(pl_dp["w"]),
                                   np.asarray(pl_tp["w"]), rtol=2e-4,
                                   atol=2e-5)


def test_mesh_2d_validates_device_count(mesh):
    from harp_tpu.parallel.mesh import mesh_2d

    with pytest.raises(ValueError, match="needs"):
        mesh_2d(4, 4)  # 16 > 8 simulated devices


def test_tp_default_constructor_works(mesh):
    """TPMLPTrainer() must be instantiable on the default topology: the
    auto-picked model axis divides every sharded layer dim."""
    tp = M.TPMLPTrainer()  # default MNIST sizes (784,512,256,10), 8 devices
    x, y = M.synthetic_mnist(n=64)
    loss, _ = tp.train_batch(x, y)
    assert np.isfinite(loss)


def test_tp_validates_divisibility(mesh):
    from harp_tpu.parallel.mesh import mesh_2d

    # layer 0 is column-parallel: its output dim 10 must divide the model axis
    with pytest.raises(ValueError, match="divisible by the model axis"):
        M.TPMLPTrainer(M.MLPConfig(sizes=(16, 10, 8)), mesh_2d(1, 8))

    tp = M.TPMLPTrainer(M.MLPConfig(sizes=(16, 32, 8)), mesh_2d(2, 4))
    x, y = M.synthetic_mnist(n=63, d=16, classes=8)  # 63 % 2 != 0
    with pytest.raises(ValueError, match="batch size"):
        tp.train_batch(x, y)


def test_fit_resident_trains_and_matches_api_contract(mesh):
    """The single-dispatch resident path converges and returns per-epoch
    stats; staging must be explicit (load_resident before fit_resident)."""
    cfg = M.MLPConfig(sizes=(16, 32, 4), lr=0.1)
    tr = M.MLPTrainer(cfg, mesh, seed=0)
    with pytest.raises(RuntimeError, match="load_resident"):
        tr.fit_resident(epochs=1)

    x, y = M.synthetic_mnist(n=512, d=16, classes=4, seed=2)
    usable = tr.load_resident(x, y, batch_size=64)
    assert usable == 512
    hist = tr.fit_resident(epochs=8)
    assert len(hist) == 8
    losses = [l for l, _ in hist]
    assert losses[-1] < 0.5 * losses[0], losses  # it actually trains
    accs = [a for _, a in hist]
    assert accs[-1] > accs[0]


def test_fit_resident_epoch_shuffle_changes_order(mesh):
    """Different seeds shuffle batch order: training still converges and
    histories differ (the on-device permutation is live, not a no-op)."""
    cfg = M.MLPConfig(sizes=(16, 32, 4), lr=0.05)
    x, y = M.synthetic_mnist(n=256, d=16, classes=4, seed=3)
    hists = []
    for seed in (0, 1):
        tr = M.MLPTrainer(cfg, mesh, seed=0)
        tr.load_resident(x, y, batch_size=32, seed=0)  # same rows
        hists.append(tr.fit_resident(epochs=3, seed=seed))
    assert hists[0] != hists[1]


def test_fit_resident_sequential_calls_keep_reshuffling(mesh):
    """Back-to-back fit_resident calls must not repeat one batch order:
    the call counter advances the shuffle key."""
    cfg = M.MLPConfig(sizes=(16, 32, 4), lr=0.05)
    tr = M.MLPTrainer(cfg, mesh, seed=0)
    x, y = M.synthetic_mnist(n=256, d=16, classes=4, seed=3)
    tr.load_resident(x, y, batch_size=32, seed=0)
    h1 = tr.fit_resident(epochs=2)
    h2 = tr.fit_resident(epochs=2)

    tr2 = M.MLPTrainer(cfg, mesh, seed=0)
    tr2.load_resident(x, y, batch_size=32, seed=0)
    g1 = tr2.fit_resident(epochs=2)
    assert g1 == h1            # same starting state → reproducible
    # a repeat-order bug would make call 2 equal a fresh run's call 1 stats
    # trajectory after manually resetting params — instead simply check the
    # counter actually changed the key path
    assert tr._shuffle_counter == 4 and tr2._shuffle_counter == 2
    assert h2 != h1


@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_quantized_grad_wire_trains(mesh, wire):
    """Quantized gradient allreduce converges close to the exact wire."""
    x, y = M.synthetic_mnist(n=512, d=16, classes=4, seed=1)
    finals = {}
    for gw in ("f32", wire):
        cfg = M.MLPConfig(sizes=(16, 32, 4), lr=0.1, grad_wire=gw)
        tr = M.MLPTrainer(cfg, mesh, seed=0)
        tr.load_resident(x, y, batch_size=64)
        finals[gw] = tr.fit_resident(epochs=8)[-1][0]
    assert finals[wire] < 1.5 * finals["f32"] + 0.05, finals


def test_bad_grad_wire_raises(mesh):
    with pytest.raises(ValueError, match="grad_wire"):
        M.MLPTrainer(M.MLPConfig(sizes=(16, 32, 4), grad_wire="fp4"), mesh)


def test_tp_rejects_grad_wire(mesh):
    with pytest.raises(ValueError, match="DP-only"):
        M.TPMLPTrainer(M.MLPConfig(sizes=(16, 32, 4), grad_wire="int8"))


def test_fit_ckpt_rejects_mismatched_sizes(mesh, tmp_path):
    x, y = M.synthetic_mnist(n=128, d=16, classes=4, seed=0)
    ck = str(tmp_path / "m")
    M.MLPTrainer(M.MLPConfig(sizes=(16, 64, 4)), mesh, seed=0).fit_ckpt(
        x, y, 2, ck, batch_size=32, ckpt_every=1)
    with pytest.raises(ValueError, match="refusing to resume"):
        M.MLPTrainer(M.MLPConfig(sizes=(16, 32, 4)), mesh, seed=0).fit_ckpt(
            x, y, 4, ck, batch_size=32, ckpt_every=1)


def test_fit_ckpt_rejects_mismatched_optimizer(mesh, tmp_path):
    # same param shapes, different optimizer state (sgd vs adam): must hit
    # the clear shape guard, not an obscure tree.unflatten structure error
    x, y = M.synthetic_mnist(n=128, d=16, classes=4, seed=0)
    ck = str(tmp_path / "m")
    M.MLPTrainer(M.MLPConfig(sizes=(16, 64, 4), optimizer="sgd"),
                 mesh, seed=0).fit_ckpt(x, y, 2, ck, batch_size=32, ckpt_every=1)
    with pytest.raises(ValueError, match="refusing to resume"):
        M.MLPTrainer(M.MLPConfig(sizes=(16, 64, 4), optimizer="adam"),
                     mesh, seed=0).fit_ckpt(x, y, 4, ck, batch_size=32,
                                            ckpt_every=1)


# ---- ZeRO-1 optimizer-state sharding (beyond-reference, round 3) ------

def _flat_params(trainer):
    return np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree.leaves(trainer.params)])


@pytest.mark.parametrize("opt", ["sgd", "momentum", "adam"])
def test_zero1_matches_replicated_stepwise(mesh, opt):
    """push(grads) + sharded optax update + pull(params) must equal the
    replicated allreduce + full update for elementwise optimizers —
    the math is identical; only the placement differs."""
    x, y = M.synthetic_mnist(n=256, d=32, classes=4, seed=0)
    outs = {}
    for z in (False, True):
        cfg = M.MLPConfig(sizes=(32, 48, 4), optimizer=opt, zero1=z)
        t = M.MLPTrainer(cfg, mesh, seed=0)
        losses = [t.train_batch(x, y)[0] for _ in range(3)]
        outs[z] = (losses, _flat_params(t))
    np.testing.assert_allclose(outs[True][0], outs[False][0], rtol=1e-5)
    np.testing.assert_allclose(outs[True][1], outs[False][1],
                               rtol=2e-5, atol=2e-6)


def test_zero1_state_is_actually_sharded(mesh):
    """The point of ZeRO-1: vector optimizer-state leaves live as
    [nw*L] arrays sharded over workers, not replicated copies."""
    cfg = M.MLPConfig(sizes=(32, 48, 4), optimizer="adam", zero1=True)
    t = M.MLPTrainer(cfg, mesh, seed=0)
    L = M.zero1_shard_len(cfg, N)
    vec_leaves = [l for l in jax.tree.leaves(t.opt_state) if l.ndim > 0]
    assert vec_leaves, "adam must have mu/nu vector state"
    for leaf in vec_leaves:
        assert leaf.shape[0] == N * L
        # sharded on the worker axis: each device holds 1/N of the rows
        assert len(leaf.sharding.device_set) == N
        shard_rows = {s.data.shape[0] for s in leaf.addressable_shards}
        assert shard_rows == {L}, shard_rows


def test_zero1_fit_resident_converges(mesh):
    x, y = M.synthetic_mnist(n=512, d=32, classes=4, seed=1)
    cfg = M.MLPConfig(sizes=(32, 64, 4), optimizer="adam", zero1=True)
    t = M.MLPTrainer(cfg, mesh, seed=0)
    t.load_resident(x, y, batch_size=128)
    stats = t.fit_resident(epochs=6)
    assert stats[-1][0] < stats[0][0]  # loss descends
    assert stats[-1][1] > 0.8          # and the net actually learns


@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_zero1_quantized_wire_trains(mesh, wire):
    """zero1 + narrow gradient wire (push_quantized): converges and stays
    close to the exact-wire trajectory."""
    x, y = M.synthetic_mnist(n=256, d=32, classes=4, seed=3)
    cfg = M.MLPConfig(sizes=(32, 48, 4), optimizer="adam", zero1=True,
                      grad_wire=wire)
    t = M.MLPTrainer(cfg, mesh, seed=0)
    losses = [t.train_batch(x, y)[0] for _ in range(5)]
    assert losses[-1] < losses[0]
    ref = M.MLPTrainer(M.MLPConfig(sizes=(32, 48, 4), optimizer="adam",
                                   zero1=True), mesh, seed=0)
    ref_losses = [ref.train_batch(x, y)[0] for _ in range(5)]
    # quantization noise perturbs, not derails
    assert abs(losses[-1] - ref_losses[-1]) < 0.3, (losses, ref_losses)


def test_zero1_ckpt_resume(mesh, tmp_path):
    """The recovery contract holds with sharded optimizer state — and the
    RESTORED state flows back into training steps with its sharding
    intact (restore must not replicate the [nw·L] leaves)."""
    x, y = M.synthetic_mnist(n=256, d=32, classes=4, seed=2)
    cfg = M.MLPConfig(sizes=(32, 48, 4), optimizer="adam", zero1=True)
    t = M.MLPTrainer(cfg, mesh, seed=0)
    ck = str(tmp_path / "z1")
    t.fit_ckpt(x, y, 2, ck, batch_size=128, ckpt_every=1)
    # a fresh trainer resumes at epoch 2 and trains two MORE epochs from
    # the restored sharded state
    t2 = M.MLPTrainer(cfg, mesh, seed=0)
    out = t2.fit_ckpt(x, y, 4, ck, batch_size=128, ckpt_every=1)
    assert len(out) == 2 and all(np.isfinite(l) for l, _ in out)
    L = M.zero1_shard_len(cfg, N)
    for leaf in jax.tree.leaves(t2.opt_state):
        if leaf.ndim > 0:
            assert {s.data.shape[0] for s in leaf.addressable_shards} == {L}
    # all epochs checkpointed → a rerun is a no-op
    t3 = M.MLPTrainer(cfg, mesh, seed=0)
    assert t3.fit_ckpt(x, y, 4, ck, batch_size=128, ckpt_every=1) == []
    for leaf in jax.tree.leaves(t3.opt_state):
        if leaf.ndim > 0:  # the pure-restore path keeps the sharding too
            assert {s.data.shape[0] for s in leaf.addressable_shards} == {L}


def test_zero1_rejected_by_tp_trainer(mesh):
    with pytest.raises(ValueError, match="DP-only"):
        M.TPMLPTrainer(M.MLPConfig(optimizer="adam", zero1=True), mesh)
