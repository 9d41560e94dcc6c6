"""Neural net / MLP — graded config #4: MNIST, gradient allreduce.

Reference parity (SURVEY.md §3.4): Harp-DAAL's ``edu.iu.daal_nn`` trains a
DAAL neural-net (MLP) data-parallel: each worker computes gradients on its
shard through DAAL's native layers, then a Harp ``allreduce`` combines
gradients before the synchronized weight update.

TPU-native design: the training step is one jitted SPMD program —
``jax.value_and_grad`` through the MLP, gradients averaged with the same
:func:`harp_tpu.parallel.collective.allreduce` verb every other app uses
(demonstrating the DP path is app-level API, not a special case), then an
optax update applied identically on every worker (weights stay replicated,
like Harp's model tables after allreduce).  The loss is written out in
:func:`loss_fn` and not optax's, whose gather of the label's logit was a
third of the step on the chip (PERF.md section 6, PR 37).  MXU notes:
batch and hidden dims padded to 128 keep the matmuls on full tiles; bf16
activations with f32 params/optimizer is the standard mixed-precision
recipe.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from harp_tpu.ingest import IngestPipeline
from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import WorkerMesh, current_mesh
from harp_tpu.utils import flightrec, prng, telemetry
from harp_tpu.utils.timing import device_sync


@dataclasses.dataclass
class MLPConfig:
    sizes: Sequence[int] = (784, 512, 256, 10)  # MNIST default (daal_nn MLP)
    lr: float = 0.01
    optimizer: str = "sgd"  # sgd | momentum | adam
    half_precision: bool = False  # bf16 activations, f32 params
    # gradient allreduce wire format: "f32" (exact, default) | "bf16" |
    # "int8" — quantized wire (collective.allreduce_quantized, EQuARX-style)
    # halves/quarters ICI/DCN gradient bytes on real pods; loss/acc metrics
    # always reduce exactly
    grad_wire: str = "f32"
    # ZeRO-1 optimizer-state sharding (beyond-reference, like TP/PP/EP):
    # instead of allreduce(grads) + a replicated optax update, the step
    # PUSHes gradient shards to their owners (psum_scatter — Harp's push
    # verb applied to the optimizer), updates only the local 1/nw slice of
    # the optimizer state, and PULLs the updated parameter shards back
    # (all_gather — Harp's pull).  Optimizer memory per chip drops nw×
    # (adam: 2× params replicated → 2×/nw), comm volume stays 2×params/
    # step like allreduce (reduce_scatter + all_gather IS ring allreduce).
    # Identical math for elementwise optimizers (sgd/momentum/adam) —
    # tests pin step-for-step equality with the replicated path.
    zero1: bool = False

    def __post_init__(self):
        if self.grad_wire not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"grad_wire must be f32|bf16|int8, got {self.grad_wire!r}")


def init_params(cfg: MLPConfig, key):
    params = []
    keys = jax.random.split(key, len(cfg.sizes) - 1)
    for k, (fan_in, fan_out) in zip(keys, zip(cfg.sizes[:-1], cfg.sizes[1:])):
        w = jax.random.normal(k, (fan_in, fan_out), jnp.float32)
        params.append({
            "w": w * jnp.sqrt(2.0 / fan_in),  # He init (ReLU net)
            "b": jnp.zeros((fan_out,), jnp.float32),
        })
    return params


def forward(params, x, cfg: MLPConfig):
    with jax.named_scope("mlp.cast"):
        h = x.astype(jnp.bfloat16) if cfg.half_precision else x
    for i, layer in enumerate(params[:-1], 1):
        with jax.named_scope(f"mlp.layer{i}"):
            w = layer["w"].astype(h.dtype)
            h = jax.nn.relu(h @ w + layer["b"].astype(h.dtype))
    last = params[-1]
    with jax.named_scope(f"mlp.layer{len(params)}"):
        logits = h @ last["w"].astype(h.dtype) + last["b"].astype(h.dtype)
        return logits.astype(jnp.float32)


def loss_fn(params, x, y, cfg: MLPConfig):
    """``(mean softmax cross-entropy, logits)`` of integer labels ``y``.

    Written out, not ``optax.softmax_cross_entropy_with_integer_labels``:
    that picks the label's logit with ``take_along_axis``, which XLA:TPU
    ran as a gather for a third of the step (PERF.md section 6, PR 37).
    Here a compare with an iota over the class axis selects it and the
    sum adds exact zeros, so the float and the gradient autodiff derives
    (softmax − one-hot) are the same.  Labels are assumed to lie in
    ``[0, classes)``: one outside selects nothing and the row's loss is
    its log-normaliser, where ``take_along_axis`` wrapped a negative
    label and filled with NaN past the last class.
    """
    logits = forward(params, x, cfg)
    with jax.named_scope("mlp.loss"):
        is_label = y[..., None] == jnp.arange(logits.shape[-1])
        label_logit = jnp.where(is_label, logits, 0).sum(-1)
        ce = jax.nn.logsumexp(logits, axis=-1) - label_logit
        return ce.mean(), logits


def make_optimizer(cfg: MLPConfig):
    if cfg.optimizer == "sgd":
        return optax.sgd(cfg.lr)
    if cfg.optimizer == "momentum":
        return optax.sgd(cfg.lr, momentum=0.9)
    if cfg.optimizer == "adam":
        return optax.adam(cfg.lr)
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")


def _step_body(tx, cfg: MLPConfig, combine):
    """The one train-step body both trainers share: value_and_grad →
    ``combine`` (the DP gradient allreduce; identity under GSPMD where XLA
    inserts the collectives) → optax update.  A change here (e.g. grad
    clipping) applies to DP and TP identically — the equivalence tests
    depend on that."""

    def step(params, opt_state, x, y):
        (loss, logits), grads = jax.value_and_grad(
            lambda p: loss_fn(p, x, y, cfg), has_aux=True
        )(params)
        with jax.named_scope("mlp.accuracy"):
            acc = (jnp.argmax(logits, -1) == y).mean()
        with jax.named_scope("mlp.combine"):
            grads, loss, acc = combine((grads, loss, acc))
        with jax.named_scope("mlp.update"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss, acc

    return step


def _grad_combine(cfg: MLPConfig):
    """The DP gradient-allreduce, honoring the configured wire format.

    Gradients may ride a quantized wire; the scalar loss/acc metrics always
    reduce exactly (they are what the user reads).
    """
    if cfg.grad_wire == "f32":
        return lambda t: C.allreduce(t, C.Combiner.AVG)
    # unknown values already rejected by MLPConfig.__post_init__
    wire = {"bf16": jnp.bfloat16, "int8": jnp.int8}[cfg.grad_wire]

    def combine(tree):
        grads, loss, acc = tree
        n = lax.axis_size(C.WORKER_AXIS)
        grads = jax.tree.map(
            lambda g: g / n, C.allreduce_quantized(grads, wire_dtype=wire))
        loss, acc = C.allreduce((loss, acc), C.Combiner.AVG)
        return grads, loss, acc

    return combine


def param_count(cfg: MLPConfig) -> int:
    return sum(fi * fo + fo for fi, fo in zip(cfg.sizes[:-1], cfg.sizes[1:]))


def zero1_shard_len(cfg: MLPConfig, n_workers: int) -> int:
    """Per-worker slice of the flattened parameter vector (ceil-padded)."""
    return -(-param_count(cfg) // n_workers)


def _zero1_grad_shard(grads, cfg: MLPConfig, nw: int, pad: int):
    """Average-reduce the gradient pytree to this worker's flat [L] slice.

    f32: one exact push (psum_scatter, AVG).  bf16: the flat quantized
    scatter.  int8: quantized PER LEAF before flattening — the same
    per-layer scale granularity :func:`allreduce_quantized` gives the
    replicated path (one global scale would zero out small-magnitude
    layers' gradients); the int32 scatter stays exact, and the dequant
    scale for each position rides a segment vector sliced to this
    worker's range.
    """
    from jax.flatten_util import ravel_pytree

    from harp_tpu.parallel.collective import quantize_to_int8

    if cfg.grad_wire == "f32":
        flat_g, _ = ravel_pytree(grads)
        return C.push(jnp.pad(flat_g, (0, pad)), C.Combiner.AVG)
    if cfg.grad_wire == "bf16":
        flat_g, _ = ravel_pytree(grads)
        return C.push_quantized(jnp.pad(flat_g, (0, pad)),
                                wire_dtype=jnp.bfloat16) / nw
    leaves = jax.tree.leaves(grads)
    # MAX-allreduce through the verb layer (one stacked collective for
    # every leaf's scale), so the ledger sees the scale exchange too
    amax = C.allreduce(jnp.stack([jnp.max(jnp.abs(g)).astype(jnp.float32)
                                  for g in leaves]), C.Combiner.MAX)
    qs, scale_segs = [], []
    for i, g in enumerate(leaves):
        q, scale = quantize_to_int8(g.reshape(-1), amax[i])
        qs.append(q)
        scale_segs.append(jnp.full((g.size,), scale, jnp.float32))
    flat_q = jnp.pad(jnp.concatenate(qs), (0, pad))
    total = C.push(flat_q.astype(jnp.int32), C.Combiner.ADD)     # exact
    scale_flat = jnp.pad(jnp.concatenate(scale_segs), (0, pad))
    L = total.shape[0]
    w = lax.axis_index(C.WORKER_AXIS)
    my_scale = lax.dynamic_slice_in_dim(scale_flat, w * L, L)
    return total.astype(jnp.float32) * my_scale / nw


def _zero1_step_body(tx, cfg: MLPConfig, nw: int):
    """ZeRO-1 twin of :func:`_step_body`: same (params, opt_state, x, y)
    → (params, opt_state, loss, acc) contract, but ``opt_state`` is this
    worker's 1/nw shard over the flattened parameter vector.  The
    gradient exchange is push (psum_scatter) + pull (all_gather) — the
    same bytes as allreduce, with the optax update sharded between them.
    """
    from jax.flatten_util import ravel_pytree

    def step(params, opt_state, x, y):
        (loss, logits), grads = jax.value_and_grad(
            lambda p: loss_fn(p, x, y, cfg), has_aux=True
        )(params)
        acc = (jnp.argmax(logits, -1) == y).mean()
        loss, acc = C.allreduce((loss, acc), C.Combiner.AVG)

        flat_p, unravel = ravel_pytree(params)
        total = flat_p.shape[0]
        L = -(-total // nw)
        pad = nw * L - total
        gsh = _zero1_grad_shard(grads, cfg, nw, pad)             # [L]
        w = lax.axis_index(C.WORKER_AXIS)
        psh = lax.dynamic_slice_in_dim(jnp.pad(flat_p, (0, pad)), w * L, L)
        updates, opt_state = tx.update(gsh, opt_state, psh)
        psh = optax.apply_updates(psh, updates)
        params = unravel(C.pull(psh)[:total])                    # [nw·L]
        return params, opt_state, loss, acc

    return step


def _opt_state_setup(mesh: WorkerMesh, cfg: MLPConfig, tx, params):
    """(initial opt_state, its shard_map spec tree) for either layout.

    Replicated (default): optax state over the full param pytree, P().
    zero1: state over a [L]-vector per worker — vector leaves live as
    [nw·L] arrays sharded on dim 0, scalar leaves (adam's count)
    replicated.  Vector leaves are built as fresh zeros: every supported
    optimizer (the make_optimizer allowlist) zero-initializes its state,
    so no device readback is needed to check.
    """
    if not cfg.zero1:
        state = jax.device_put(tx.init(params), mesh.replicated())
        return state, P()
    nw = mesh.num_workers
    L = zero1_shard_len(cfg, nw)
    local = tx.init(jnp.zeros((L,), jnp.float32))

    def globalize(leaf):
        if leaf.ndim == 0:
            return jax.device_put(leaf, mesh.replicated())
        return mesh.shard_array(
            np.zeros((nw * L,) + leaf.shape[1:], np.dtype(leaf.dtype)), 0)

    state = jax.tree.map(globalize, local)
    return state, _opt_specs_for(mesh, cfg)


def _opt_specs_for(mesh: WorkerMesh, cfg: MLPConfig):
    """shard_map specs for the optimizer state — derived from cfg alone,
    so make_train_step/make_epoch_fn can never be handed mismatched
    specs for a zero1 config."""
    if not cfg.zero1:
        return P()
    local = jax.eval_shape(  # structure only — no device work
        make_optimizer(cfg).init,
        jax.ShapeDtypeStruct((zero1_shard_len(cfg, mesh.num_workers),),
                             jnp.float32))
    return jax.tree.map(lambda a: P() if a.ndim == 0 else mesh.spec(0),
                        local)


def _pick_step_body(mesh: WorkerMesh, cfg: MLPConfig, tx):
    if cfg.zero1:
        return _zero1_step_body(tx, cfg, mesh.num_workers)
    # the graded pattern: gradient allreduce through the app-level verb
    return _step_body(tx, cfg, _grad_combine(cfg))


def make_train_step(mesh: WorkerMesh, cfg: MLPConfig):
    """Compile the data-parallel training step (the daal_nn hot loop).

    The optimizer-state placement follows ``cfg.zero1`` automatically
    (specs derived internally — callers cannot hand mismatched ones);
    pair with :func:`_opt_state_setup` for the matching initial state.
    """
    tx = make_optimizer(cfg)
    step = _pick_step_body(mesh, cfg, tx)
    opt_specs = _opt_specs_for(mesh, cfg)
    return jax.jit(
        mesh.shard_map(
            step,
            in_specs=(P(), opt_specs, mesh.spec(0), mesh.spec(0)),
            out_specs=(P(), opt_specs, P(), P()),
        )
    ), tx


def epoch_batch_order(key, epoch, n_batches: int):
    """The batches epoch ``epoch`` of a resident run visits, in order:
    batch ``i`` is rows ``[i * batch_per_worker, (i + 1) *
    batch_per_worker)`` of every worker's shard.  ``key`` is the raw key
    bits the run was handed; the epoch index is folded in, so every
    epoch reshuffles.  The one definition: :func:`make_epoch_fn` scans
    over it and :meth:`MLPTrainer.resident_batch_order` hands it out."""
    return jax.random.permutation(
        jax.random.fold_in(jax.random.wrap_key_data(key), epoch), n_batches)


def make_epoch_fn(mesh: WorkerMesh, cfg: MLPConfig, batch_per_worker: int,
                  n_batches: int, epochs: int = 1):
    """Compile ``epochs`` epochs over a device-RESIDENT shard as ONE program.

    Harp-DAAL NN iterates minibatches of an in-memory NumericTable; the
    TPU analogue keeps the shard in HBM and scans batch steps (and epochs)
    on device — one dispatch and one readback for the whole run, where a
    host loop pays a round trip every step of a few tens of microseconds
    of device work (PERF.md has the measured figures).
    Batch order reshuffles each epoch (:func:`epoch_batch_order`; the key
    is replicated, so workers visit their shards in step).
    Returns per-epoch (last-batch loss, acc) arrays.
    """
    tx = make_optimizer(cfg)
    step = _pick_step_body(mesh, cfg, tx)
    opt_specs = _opt_specs_for(mesh, cfg)

    def run(params, opt_state, xs, ys, key):
        def epoch(carry, e):
            params, opt_state = carry
            with jax.named_scope("mlp.order"):
                order = epoch_batch_order(key, e, n_batches)

            def body(c, i):
                p, o = c
                with jax.named_scope("mlp.batch"):
                    xb = lax.dynamic_slice_in_dim(
                        xs, i * batch_per_worker, batch_per_worker, 0)
                    yb = lax.dynamic_slice_in_dim(
                        ys, i * batch_per_worker, batch_per_worker, 0)
                p, o, loss, acc = step(p, o, xb, yb)
                return (p, o), (loss, acc)

            # alone on an op's path: the scan's own slices and stacking
            with jax.named_scope("mlp.steps"):
                (params, opt_state), (losses, accs) = lax.scan(
                    body, (params, opt_state), order)
            return (params, opt_state), (losses[-1], accs[-1])

        (params, opt_state), (losses, accs) = lax.scan(
            epoch, (params, opt_state), jnp.arange(epochs))
        return params, opt_state, losses, accs

    return jax.jit(
        mesh.shard_map(
            run,
            in_specs=(P(), opt_specs, mesh.spec(0), mesh.spec(0), P()),
            out_specs=(P(), opt_specs, P(), P()),
        )
    ), tx


def _effective_batch(batch_size: int, n: int, n_workers: int) -> int:
    """Batch size actually used: capped at n, rounded down to a worker
    multiple, floored at one sample per worker.  Shared by fit and
    load_resident so both paths train with the same effective batch for
    the same argument."""
    return max(n_workers, (min(batch_size, n) // n_workers) * n_workers)


def _batch_reader(x, y, batch_size, order):
    """Stage-1 reader for the shared ingest pipeline (PR 8): contiguous
    ZERO-COPY views of the caller's arrays.  Shuffling permutes BATCH
    indices (``order``, re-drawn per epoch by the caller), never rows —
    the pre-PR loop gathered ``x[perm]`` batch by batch, a full
    fancy-index copy of the dataset every epoch; a view costs nothing
    and the cast/H2D stages downstream touch only one batch at a time
    (pinned by tests/test_ingest.py: the reader output shares memory
    with the input)."""

    def read(j):
        lo = int(order[j]) * batch_size
        return x[lo:lo + batch_size], y[lo:lo + batch_size]

    return read


class MLPTrainer:
    """Host driver (the mapCollective residue for edu.iu.daal_nn)."""

    def __init__(self, cfg: MLPConfig | None = None, mesh: WorkerMesh | None = None,
                 seed=0):
        self.mesh = mesh or current_mesh()
        self.cfg = cfg or MLPConfig()
        self.params = jax.device_put(
            init_params(self.cfg, jax.random.key(seed)), self.mesh.replicated()
        )
        tx = make_optimizer(self.cfg)
        self.opt_state, self._opt_specs = _opt_state_setup(
            self.mesh, self.cfg, tx, self.params)
        self._step, _ = make_train_step(self.mesh, self.cfg)
        self._forward = flightrec.track(
            jax.jit(lambda p, v: forward(p, v, self.cfg)), "mlp.forward")
        self._epoch_fns: dict = {}
        self._shuffle_counter = 0
        # optimizer steps this trainer has run, whichever path ran them
        self.steps_run = 0

    def train_batch(self, x, y):
        """x: [b, features], y: [b] int labels; b divisible by num_workers."""
        x = self.mesh.shard_array(np.asarray(x, np.float32), 0)
        y = self.mesh.shard_array(np.asarray(y, np.int32), 0)
        self.params, self.opt_state, loss, acc = self._step(
            self.params, self.opt_state, x, y
        )
        self.steps_run += 1
        return float(device_sync(loss)), float(device_sync(acc))

    def _on_mesh(self, a) -> bool:
        """Is ``a`` a device array already row-sharded over this
        trainer's mesh?"""
        return isinstance(a, jax.Array) and a.sharding.is_equivalent_to(
            self.mesh.sharding(self.mesh.spec(0, ndim=a.ndim)), a.ndim)

    def load_resident(self, x, y, batch_size=8192, seed=0):
        """Stage the dataset in HBM for :meth:`fit_resident`.

        Rows stage in input order; when the batch-divisibility trim must
        drop rows it drops a uniform random subset (``seed``), so the
        trim stays unbiased without the pre-PR-8 full-row host reshuffle
        (a whole extra dataset copy).  Batch ORDER still reshuffles on
        device every epoch (:func:`make_epoch_fn`).  Host arrays cross to
        the device here, once, not inside the training loop; arrays
        already row-sharded over this trainer's mesh are taken as they
        are (cast and trim on the device: the trim is a gather, a second
        table while it runs).
        Returns the usable sample count.
        """
        n = x.shape[0]
        nw = self.mesh.num_workers
        if n < nw:
            raise ValueError(f"need at least {nw} samples (one per worker), got {n}")
        batch_size = _effective_batch(batch_size, n, nw)
        usable = (n // batch_size) * batch_size
        # the dropped rows are a uniform random subset (order preserved),
        # so the trim stays unbiased without a full-row reshuffle
        keep = None
        if usable < n:
            keep = np.sort(np.random.default_rng(seed).choice(
                n, size=usable, replace=False))
        with telemetry.span("mlp.load_resident", rows=usable,
                            trimmed=n - usable,
                            bytes=usable * (x.shape[1] * 4 + 4)):
            if self._on_mesh(x) and self._on_mesh(y):
                xs, ys = x.astype(jnp.float32), y.astype(jnp.int32)
                if keep is not None:
                    trim = flightrec.track(jax.jit(
                        lambda a, b, k: (jnp.take(a, k, axis=0),
                                         jnp.take(b, k, axis=0)),
                        out_shardings=(xs.sharding, ys.sharding)),
                        "mlp.trim")
                    xs, ys = trim(xs, ys, self.mesh.shard_array(
                        keep.astype(np.int32), None))
            else:
                # rows stage in INPUT order (zero extra host copies when
                # x is already f32 and nothing is trimmed)
                xs_host = np.asarray(x, np.float32)
                ys_host = np.asarray(y, np.int32)
                if keep is not None:
                    xs_host, ys_host = xs_host[keep], ys_host[keep]
                xs = self.mesh.shard_array(xs_host, 0)
                ys = self.mesh.shard_array(ys_host, 0)
        self._resident = (xs, ys, batch_size // nw, usable // batch_size)
        return usable

    def _resident_key(self, seed):
        """Raw threefry key bits of the next :meth:`fit_resident` call,
        built on host: ``jax.random.PRNGKey(int)`` specializes on the
        Python int, so distinct seeds would each trigger a (remote)
        compile.  The call counter advances the key so sequential calls
        (natural when reusing a compiled epoch count) keep reshuffling
        instead of repeating one order."""
        return prng.key_bits(seed + 1 + self._shuffle_counter)

    def resident_batch_order(self, epochs=1, seed=0):
        """The batches the NEXT ``fit_resident(epochs, seed)`` visits:
        int array ``[epochs, n_batches]`` (:func:`epoch_batch_order`)."""
        if getattr(self, "_resident", None) is None:
            raise RuntimeError(
                "call load_resident() before resident_batch_order()")
        key, nb = self._resident_key(seed), self._resident[3]
        return np.stack([np.asarray(epoch_batch_order(key, e, nb))
                         for e in range(epochs)])

    def fit_resident(self, epochs=1, seed=0):
        """Train on the :meth:`load_resident`-staged data — ALL epochs as
        one device program (see :func:`make_epoch_fn`), batch order
        reshuffled on device each epoch.  Returns [(last_loss, last_acc)]
        per epoch.
        """
        if getattr(self, "_resident", None) is None:
            raise RuntimeError("call load_resident() before fit_resident()")
        xs, ys, bpw, nb = self._resident
        fn = self._epoch_fns.get((bpw, nb, epochs))
        if fn is None:
            fn = self._epoch_fns[(bpw, nb, epochs)] = flightrec.track(
                make_epoch_fn(self.mesh, self.cfg, bpw, nb, epochs)[0],
                "mlp.epochs")
        key = self._resident_key(seed)
        self._shuffle_counter += epochs
        # the scan body's traced comm sites execute once per optimizer step
        with telemetry.span("mlp.epochs", epochs=epochs), \
                telemetry.ledger.run("mlp.epochs", steps=epochs * nb):
            self.params, self.opt_state, losses, accs = fn(
                self.params, self.opt_state, xs, ys, key)
            stats = flightrec.readback(                    # one readback
                jnp.stack([losses, accs], axis=1))
        self.steps_run += epochs * nb
        return [(float(l), float(a)) for l, a in stats]

    def fit_ckpt(self, x, y, epochs, ckpt_dir=None, *, batch_size=8192,
                 ckpt_every=5, max_restarts=3, fault=None, seed=0):
        """Epoch training with checkpoint/resume — the same recovery
        contract as MF-SGD/LDA ``fit()`` (SURVEY.md §6: restart-from-entry
        before the first checkpoint, resume installs restored state, fault
        without ckpt_dir refused).  One epoch = one resident device program
        (:meth:`fit_resident`); params AND optimizer state checkpoint, so a
        resumed adam/momentum run continues the same trajectory.  Returns
        [(last_loss, last_acc)] for the epochs this call ran.
        """
        from harp_tpu.utils.fault import check_restored_shapes, fit_epochs

        self.load_resident(x, y, batch_size=batch_size, seed=seed)
        history: list = []

        def set_state(state):
            # opt_state too: matching params but a different optimizer
            # (sgd vs adam) would otherwise die inside tree.unflatten with
            # an obscure structure error instead of this clear refusal
            check_restored_shapes([
                ("params", state["params"], self.params),
                ("opt_state", state["opt_state"], self.opt_state),
            ])
            if not isinstance(jax.tree.leaves(state["params"])[0], jax.Array):
                # a checkpoint restore yields plain containers; rebuild on
                # the LIVE treedefs so optax's named-tuple states survive
                def put_like(template, restored, spec_tree=None):
                    leaves = [np.asarray(v) for v in jax.tree.leaves(restored)]
                    tdef = jax.tree.structure(template)
                    if spec_tree is None:
                        return jax.device_put(jax.tree.unflatten(tdef, leaves),
                                              self.mesh.replicated())
                    # zero1: restore each leaf to ITS sharding — replicating
                    # the [nw·L] state on every chip would transiently cost
                    # the nw× memory zero1 exists to avoid (the spec tree is
                    # leaf-aligned with the state by construction)
                    specs = jax.tree.leaves(
                        spec_tree, is_leaf=lambda s: isinstance(s, P))
                    assert len(specs) == len(leaves), (specs, len(leaves))
                    placed = [jax.device_put(l, self.mesh.sharding(sp))
                              for l, sp in zip(leaves, specs)]
                    return jax.tree.unflatten(tdef, placed)

                self.params = put_like(self.params, state["params"])
                self.opt_state = put_like(
                    self.opt_state, state["opt_state"],
                    None if self._opt_specs == P() else self._opt_specs)
            else:
                self.params = state["params"]
                self.opt_state = state["opt_state"]
            self._shuffle_counter = int(np.asarray(state["shuffle"]))

        fit_epochs(
            lambda: history.append(self.fit_resident(epochs=1, seed=seed)[0]),
            lambda: {"params": self.params, "opt_state": self.opt_state,
                     "shuffle": np.int64(self._shuffle_counter)},
            set_state,
            epochs, ckpt_dir, ckpt_every=ckpt_every,
            max_restarts=max_restarts, fault=fault,
            phase="mlp.epochs",
        )
        return history

    def fit(self, x, y, batch_size=8192, epochs=1, shuffle_seed=0,
            prefetch=2):
        """Host-streamed epoch training through the shared ingest
        pipeline (:mod:`harp_tpu.ingest`, PR 8): batches are contiguous
        zero-copy views of ``x``/``y``, the per-epoch shuffle permutes
        BATCH indices, and with ``prefetch >= 2`` batch j+1's f32/int32
        cast and H2D overlap batch j's step.  The pre-PR loop gathered
        ``x[perm]`` per batch — a full fancy-index copy of the dataset
        every epoch.  (Batch COMPOSITION is now fixed contiguous blocks
        in shuffled order — the same fixed-composition property the
        resident path has after staging.)  Each epoch's loop runs under
        a warn-mode flight budget: exactly the batch bytes on the wire,
        zero recompiles after the first epoch."""
        n = x.shape[0]
        nw = self.mesh.num_workers
        if n < nw:
            raise ValueError(f"need at least {nw} samples (one per worker), got {n}")
        batch_size = _effective_batch(batch_size, n, nw)
        usable = (n // batch_size) * batch_size
        n_batches = usable // batch_size
        x = np.asarray(x)
        y = np.asarray(y)
        rng = np.random.default_rng(shuffle_seed)
        order = np.arange(n_batches)  # re-permuted in place per epoch

        def prep(batch):
            xb, yb = batch
            return np.asarray(xb, np.float32), np.asarray(yb, np.int32)

        def ship(batch):
            xb, yb = batch
            return (self.mesh.shard_array(xb, 0),
                    self.mesh.shard_array(yb, 0))

        epoch_bytes = usable * (x.shape[1] * 4 + 4)  # f32 rows + i32 labels
        history = []
        with IngestPipeline(_batch_reader(x, y, batch_size, order), prep,
                            ship, depth=max(1, prefetch),
                            tag="mlp.fit") as pipe:
            for e in range(epochs):
                order[:] = rng.permutation(n_batches)
                with telemetry.budget(h2d_bytes=epoch_bytes,
                                      compiles=None if e == 0 else 0,
                                      action="warn", tag="mlp.fit.ingest"):
                    for xb, yb in pipe.stream(n_batches):
                        self.params, self.opt_state, loss, acc = self._step(
                            self.params, self.opt_state, xb, yb)
                        self.steps_run += 1
                        history.append((float(device_sync(loss)),
                                        float(device_sync(acc))))
        return history

    def predict(self, x):
        # device_put, not jnp.asarray: host data must ride the counted
        # H2D path, never risk baking in as a compile-time literal (HL003)
        xs = jax.device_put(np.asarray(x, np.float32))
        return np.asarray(self._forward(self.params, xs))

    def accuracy(self, x, y):
        return float((self.predict(x).argmax(-1) == np.asarray(y)).mean())


class TPMLPTrainer:
    """Tensor-parallel MLP on a 2-D (data × model) mesh — GSPMD style.

    Beyond-reference extension (Harp has no TP — SURVEY.md §3.5): layers
    alternate Megatron-style column-parallel (w sharded on the output dim)
    and row-parallel (input dim), the batch shards over the data axis, and
    XLA inserts every collective from the sharding annotations alone — no
    ``shard_map``, no explicit verbs.  Numerics match the DP trainer (same
    global mean loss/grads), asserted in tests.
    """

    def __init__(self, cfg: MLPConfig | None = None, mesh=None, seed=0):
        from jax.sharding import NamedSharding

        from harp_tpu.parallel.mesh import mesh_2d

        self.cfg = cfg or MLPConfig()
        if self.cfg.zero1:
            raise ValueError(
                "zero1 is DP-only: the TP trainer's optimizer state follows "
                "the GSPMD param shardings; silently replicating it would "
                "betray the memory contract zero1 promises")
        if self.cfg.grad_wire != "f32":
            raise ValueError(
                f"grad_wire={self.cfg.grad_wire!r} is DP-only: under GSPMD "
                "XLA inserts the TP collectives from sharding annotations, "
                "so there is no explicit allreduce to quantize — use "
                "MLPTrainer for a quantized gradient wire")
        if mesh is None:
            # largest model axis that divides every SHARDED layer dim (the
            # output dim of even layers, input dim of odd ones) AND the
            # device count — so the no-arg constructor works on any host
            import math

            sizes = self.cfg.sizes
            sharded_dims = [sizes[i + 1] if i % 2 == 0 else sizes[i]
                            for i in range(len(sizes) - 1)]
            g = math.gcd(*sharded_dims)
            n_dev = len(jax.devices())
            n_model = max(d for d in range(1, min(g, n_dev) + 1)
                          if g % d == 0 and n_dev % d == 0)
            mesh = mesh_2d(n_dev // n_model, n_model)
        self.mesh = mesh
        data_ax, model_ax = self.mesh.axis_names
        n_model = self.mesh.shape[model_ax]
        self._n_data = self.mesh.shape[data_ax]
        sizes = self.cfg.sizes
        for i, (fan_in, fan_out) in enumerate(zip(sizes[:-1], sizes[1:])):
            sharded_dim = fan_out if i % 2 == 0 else fan_in
            if sharded_dim % n_model != 0:
                raise ValueError(
                    f"TP needs layer {i}'s "
                    f"{'output' if i % 2 == 0 else 'input'} dim "
                    f"({sharded_dim}) divisible by the model axis "
                    f"({n_model}); adjust MLPConfig.sizes or the mesh")
        params = init_params(self.cfg, jax.random.key(seed))
        sharded = []
        for i, layer in enumerate(params):
            if i % 2 == 0:  # column-parallel: shard the output dim
                w_s, b_s = P(None, model_ax), P(model_ax)
            else:           # row-parallel: shard the input dim
                w_s, b_s = P(model_ax, None), P()
            sharded.append({
                "w": jax.device_put(layer["w"], NamedSharding(self.mesh, w_s)),
                "b": jax.device_put(layer["b"], NamedSharding(self.mesh, b_s)),
            })
        self.params = sharded
        tx = make_optimizer(self.cfg)
        self.opt_state = tx.init(self.params)
        self._batch_sharding = NamedSharding(self.mesh, P(data_ax))
        # same body as the DP trainer; GSPMD inserts the collectives, so
        # the combine step is the identity
        self._step = flightrec.track(
            jax.jit(_step_body(tx, self.cfg, lambda t: t),
                    donate_argnums=(0, 1)), "mlp.tp_step")

    def train_batch(self, x, y):
        """x: [b, features], y: [b]; b must be divisible by the data axis."""
        if len(x) % self._n_data != 0:
            raise ValueError(
                f"batch size {len(x)} not divisible by the data axis "
                f"({self._n_data}) — round the batch like MLPTrainer.fit does")
        x = jax.device_put(np.asarray(x, np.float32), self._batch_sharding)
        y = jax.device_put(np.asarray(y, np.int32), self._batch_sharding)
        self.params, self.opt_state, loss, acc = self._step(
            self.params, self.opt_state, x, y)
        return float(device_sync(loss)), float(device_sync(acc))


def synthetic_mnist(n=60_000, d=784, classes=10, seed=0, noise=0.8):
    """MNIST-shaped synthetic task (no network access in this environment):
    images are class-prototype + noise, so a real decision boundary exists."""
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(classes, d)).astype(np.float32)
    y = rng.integers(0, classes, n).astype(np.int32)
    x = 0.5 * protos[y] + rng.normal(size=(n, d)).astype(np.float32) * noise
    return x, y


def benchmark(n=60_000, batch=8192, steps=50, mesh=None, cfg=None, warmup=5):
    """Samples/sec through the DP training step on MNIST shapes.

    Headline is the device-resident epoch path (``fit_resident`` — data in
    HBM, one dispatch per epoch, like DAAL iterating an in-memory
    NumericTable); ``samples_per_sec_hostloop`` times the per-batch host
    dispatch loop (a host input pipeline) for comparison.  What either
    reads on a chip is in PERF.md (the cell ``mlp-epochs``).
    """
    mesh = mesh or current_mesh()
    cfg = cfg or MLPConfig()
    trainer = MLPTrainer(cfg, mesh)
    x, y = synthetic_mnist(n=max(n, batch), d=cfg.sizes[0],
                           classes=cfg.sizes[-1])
    xb = trainer.mesh.shard_array(x[:batch], 0)
    yb = trainer.mesh.shard_array(y[:batch], 0)

    # host-loop path: the jitted per-batch step, dispatched per batch
    trainer.train_batch(x[:batch], y[:batch])  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        trainer.params, trainer.opt_state, loss, acc = trainer._step(
            trainer.params, trainer.opt_state, xb, yb
        )
    device_sync(loss)
    dt_host = time.perf_counter() - t0

    # resident path: whole shard staged in HBM once, scan batches per epoch.
    # Enough epochs that the one end-of-call readback is amortized, not
    # measured.
    usable = trainer.load_resident(x, y, batch_size=batch)
    epochs = max(8, (steps * batch) // usable) * 8
    # warm with the SAME epoch count: the compiled program is keyed on it,
    # so a different count would put the compile inside the timed region
    trainer.fit_resident(epochs=epochs)
    t0 = time.perf_counter()
    hist = trainer.fit_resident(epochs=epochs)
    dt_res = time.perf_counter() - t0
    return {
        "samples_per_sec": usable * epochs / dt_res,
        "samples_per_sec_hostloop": batch * steps / dt_host,
        "steps_per_sec": usable * epochs / batch / dt_res,
        "loss": hist[-1][0],
        "acc": hist[-1][1],
        # the quantized gradient wire's quality field (PR 8: a bf16 or
        # int8 wire is judged on train_acc before speed — a degraded
        # wire must not win on speed)
        "train_acc": hist[-1][1],
        "grad_wire": cfg.grad_wire,
        "batch": batch,
        "num_workers": mesh.num_workers,
        "half_precision": cfg.half_precision,
    }


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description="harp-tpu MLP (edu.iu.daal_nn parity)")
    p.add_argument("--batch", type=int, default=8192)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--optimizer", default="sgd")
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--train", action="store_true", help="2-epoch training demo")
    args = p.parse_args(argv)
    cfg = MLPConfig(optimizer=args.optimizer, half_precision=args.bf16)
    from harp_tpu.utils.metrics import benchmark_json

    if args.train:
        x, y = synthetic_mnist()
        tr = MLPTrainer(cfg)
        hist = tr.fit(x, y, batch_size=args.batch, epochs=2)
        # one-line JSON like every other CLI branch, so a teed line is a
        # parseable BENCH_local.jsonl row (ADVICE r4)
        print(benchmark_json("mlp_fit_cli", {
            "first_loss": float(hist[0][0]), "last_loss": float(hist[-1][0]),
            "train_acc": float(tr.accuracy(x[:10000], y[:10000]))}))
    else:
        print(benchmark_json("mlp_cli", benchmark(
            batch=args.batch, steps=args.steps, cfg=cfg)))


if __name__ == "__main__":
    main()
