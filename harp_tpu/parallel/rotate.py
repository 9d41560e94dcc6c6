"""Model-rotation pipeline: Harp's dymoro, TPU-native — now chunked.

Reference parity (SURVEY.md §3.1, §3.5, §4.3): ``edu.iu.dymoro.Rotator`` +
``Scheduler`` implement Harp's signature optimization — while worker threads
update the model slice currently resident, the *next* slice is already in
flight from the ring neighbor, so communication hides behind compute.  A
timer bounds each compute phase so all workers rotate in lockstep.

TPU-native version: a ``lax.scan`` whose body runs the compute step on the
resident slice and then issues the ``ppermute``.  Overlap of transfer with
compute depends on the data flow: for **read-only** step functions XLA's
async scheduler overlaps the rotation with the next step's compute; for
**slice-updating** step functions (MF-SGD, LDA) a whole-slice rotation
serializes — a mutated partition cannot leave before the update finishes,
the constraint Harp's Rotator also has.  The cure is **chunking**
(``n_chunks > 1``): each worker's slice splits into ``n_chunks`` sub-slices
that alternate compute / in-flight roles, so the chunk updated at step
``t-1`` travels the ring while step ``t`` computes on the next one — a
software double buffer (TACCL's chunked-pipelining observation, PAPERS.md
arXiv:2111.04867, applied to the rotate collective).  ``n_chunks=2`` is
exactly the two-halves schedule MF-SGD and LDA used to hand-roll;
``wire`` selects the ring payload format (``"exact"`` ppermute, or the
quantized :func:`harp_tpu.parallel.collective.rotate_quantized` wire).
Lockstep comes free: SPMD programs advance together, so the timer-bounded
dynamic scheduling is replaced by fixed work per step (SURVEY.md §8 "hard
parts" — convergence must be validated per app, which the app tests do).

This is structurally the ring-attention ppermute pattern; long-context
sequence parallelism falls out of the same primitive (see
``harp_tpu.ops.ring_attention`` for the demonstration).
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from harp_tpu.parallel.mesh import WORKER_AXIS
from harp_tpu.parallel.collective import ShardSpec, reshard

#: ring payload formats for the pipelined rotation (see rotate_pipeline)
ROTATE_WIRES = ("exact", "bf16", "int8")


def _wire_rotate(wire: str | None, shift: int, axis: str):
    """Resolve a ``wire`` name to the ring-hop move for in-flight chunks.

    PR 11: a ring hop IS a reshard between ring-shifted layouts, so the
    former bespoke rotate/rotate_quantized dispatch collapses into ONE
    ``reshard(blocked(0), blocked(0, shift), wire=...)`` call — the
    equivalence-pinned shim behind every rotation app (mfsgd, lda, ccd,
    ring attention ride this pipeline).  The lowering emits the exact
    same ``ppermute`` (same perm, same payload; quantized wires keep
    the one-rounding stacked-pmax contract), pinned bit-for-bit against
    the direct verb by tests/test_reshard.py and the apps' numpy
    goldens; the CommLedger verb at these sites is now ``reshard``.
    """
    if wire is None:
        wire = "exact"
    if wire not in ROTATE_WIRES:
        raise ValueError(
            f"wire must be one of {ROTATE_WIRES}, got {wire!r}")
    src, dst = ShardSpec.blocked(0), ShardSpec.blocked(0, shift=shift)
    return lambda tree: reshard(tree, src, dst, axis=axis, wire=wire)


def _split_chunks(tree: Any, n_chunks: int, axis: int):
    """Split every leaf's ``axis`` into ``n_chunks`` equal chunks, stacked
    on a new leading chunk dimension."""
    def split(x):
        if x.shape[axis] % n_chunks:
            raise ValueError(
                f"model slice dim {axis} of size {x.shape[axis]} does not "
                f"split into {n_chunks} equal rotation chunks")
        m = x.shape[axis] // n_chunks
        shape = x.shape[:axis] + (n_chunks, m) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    return jax.tree.map(split, tree)


def _join_chunks(tree: Any, axis: int):
    """Inverse of :func:`_split_chunks`: merge the leading chunk dimension
    back into ``axis``."""
    def join(x):
        y = jnp.moveaxis(x, 0, axis)
        return y.reshape(y.shape[:axis]
                         + (y.shape[axis] * y.shape[axis + 1],)
                         + y.shape[axis + 2:])

    return jax.tree.map(join, tree)


def rotate_pipeline(
    step_fn: Callable[[Any, Any, Any], Any],
    carry: Any,
    model_slice: Any,
    *,
    n_steps: int | None = None,
    shift: int = 1,
    axis: str = WORKER_AXIS,
    n_chunks: int = 1,
    wire: str = "exact",
    chunk_axis: int = 0,
):
    """Run one rotation epoch of ``carry = step_fn(carry, chunk, t)``.

    ``n_chunks=1`` (default): each step computes on the whole resident
    slice, then rotates it onward — when ``gcd(shift, num_workers) == 1``,
    ``n_steps == num_workers`` steps visit every slice on every worker
    exactly once and leave each slice back home — one full Harp "epoch" of
    model rotation.  A ``shift`` sharing a factor with the ring size cycles
    through only ``num_workers/gcd`` slices; the default full-revolution
    mode rejects it rather than silently training on a subset of the model.
    With an update-free ``step_fn`` XLA overlaps the transfer with the next
    step's compute; with updates the handoff serializes (Harp's constraint
    too).

    ``n_chunks=C > 1``: the slice splits into C equal chunks along
    ``chunk_axis`` and the epoch becomes ``C * num_workers`` steps of a
    software double buffer — at step ``t`` the chunk updated at step
    ``t-1`` is in flight (its ``ppermute`` has no data dependency on this
    step's compute, so XLA overlaps it) while ``step_fn`` runs on the next
    resident chunk.  ``C=2`` reproduces the bespoke two-halves schedule
    bit-for-bit (``resident_half_index``); larger C shrinks each transfer
    and exposes more overlap slots at the cost of more scan steps.  Apps
    map step ``t`` to the resident chunk's global index with
    :func:`resident_chunk_index`.  ``n_steps`` must be left ``None`` (the
    full revolution) in chunked mode.

    ``wire`` selects the ring payload: ``"exact"`` (default — bit-exact
    ppermute), ``"bf16"`` or ``"int8"`` (the
    :func:`~harp_tpu.parallel.collective.rotate_quantized` formats; each
    hop re-rounds the chunk, so an epoch accumulates at most one rounding
    per hop a chunk travels).

    Args:
      step_fn: ``(carry, chunk, step_index) -> (carry, chunk)``; may update
        the chunk (MF-SGD does) — the updated chunk is what rotates onward,
        exactly like Harp rotating the mutated partition.
      carry: loop state local to the worker (e.g. W factor, rng key, loss).
      model_slice: this worker's resident slice of the global model (pytree).
      n_steps: unchunked mode only — defaults to the ring size (one full
        revolution).
      shift: ring direction/stride, as in Harp's rotate.

    Returns:
      ``(carry, model_slice)`` after the final step, chunks reassembled in
      home order.

    Must be called inside ``shard_map`` (device view).
    """
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    wrotate = _wire_rotate(wire, shift, axis)

    if n_chunks == 1:
        if n_steps is None:
            n_steps = lax.axis_size(axis)
            if math.gcd(shift % n_steps, n_steps) != 1:
                raise ValueError(
                    f"shift={shift} shares a factor with the ring size {n_steps}: "
                    f"a full revolution would visit only {n_steps // math.gcd(shift % n_steps, n_steps)} "
                    f"of {n_steps} slices; pass n_steps explicitly if that is intended"
                )

        def body(state, t):
            c, cur = state
            c, cur = step_fn(c, cur, t)
            # Rotation of the (possibly updated) slice. With an update-free
            # step_fn XLA overlaps this transfer with the next iteration's
            # compute; with updates it is the serialized handoff Harp also
            # has — use n_chunks > 1 to overlap through updates.
            nxt = wrotate(cur)
            return (c, nxt), None

        (carry, model_slice), _ = lax.scan(
            body, (carry, model_slice), jnp.arange(n_steps)
        )
        return carry, model_slice

    if n_steps is not None:
        raise ValueError(
            "chunked mode runs the full revolution (n_chunks * ring size "
            "steps); n_steps must be None")
    n = lax.axis_size(axis)
    if math.gcd(shift % n, n) != 1:
        raise ValueError(
            f"shift={shift} shares a factor with the ring size {n}: chunks "
            "would revisit a worker subset instead of covering the ring")

    buf = _split_chunks(model_slice, n_chunks, chunk_axis)
    # local chunks 0..C-2 queue up for compute; chunk C-1 starts in flight
    # (it is computed by workers w+shift .. w+n*shift and lands home on the
    # last step) — at C=2 this is exactly the former bespoke
    # computing/inflight half-slice split of mfsgd/lda.
    queue = jax.tree.map(lambda a: a[:-1], buf)
    inflight = jax.tree.map(lambda a: a[-1], buf)

    def body(state, t):
        c, q, infl = state
        received = wrotate(infl)  # no dep on this step's compute: overlaps
        cur = jax.tree.map(lambda a: a[0], q)
        c, cur = step_fn(c, cur, t)
        # pop the computed head; the received chunk joins the queue tail
        # (it computes C-1 steps from now, giving every chunk a period of
        # exactly C steps per worker hop — full (worker, chunk) coverage)
        q = jax.tree.map(
            lambda a, r: jnp.concatenate([a[1:], r[None]], axis=0),
            q, received)
        return (c, q, cur), None

    (carry, queue, inflight), _ = lax.scan(
        body, (carry, queue, inflight), jnp.arange(n_chunks * n)
    )
    # after C·n steps home chunk p sits at queue position p (p < C-1) and
    # chunk C-1 — computed on its home worker at the final step — is the
    # outgoing `inflight`; reassemble in home order
    buf = jax.tree.map(
        lambda a, b: jnp.concatenate([a, b[None]], axis=0), queue, inflight)
    return carry, _join_chunks(buf, chunk_axis)


def rotate_pipeline_resident(
    step_fn: Callable[[Any, Any, Any, Any], Any],
    carry: Any,
    model_slice: Any,
    *,
    n_chunks: int,
    shift: int = 1,
    axis: str = WORKER_AXIS,
    wire: str = "exact",
    chunk_axis: int = 0,
):
    """The chunked :func:`rotate_pipeline`, for a slice too large to copy:
    the slice stays in ONE buffer for the whole epoch and a chunk is a
    range of ``chunk_axis`` that ``step_fn`` addresses in place.

    Same schedule, same data, bit for bit (pinned against
    :func:`rotate_pipeline` by tests/test_lda_layout.py): chunk ``p`` of
    the local slice is slot ``p``, the range ``[p * m, (p + 1) * m)`` of
    ``chunk_axis``; step ``t`` computes on slot ``t % n_chunks`` while
    slot ``(t - 1) % n_chunks`` (computed the step before; at step 0 the
    last slot) crosses the ring, and after ``n_chunks * num_workers``
    steps every chunk is home in its own slot.  What differs is what
    moves on the device.  :func:`rotate_pipeline` hands ``step_fn`` the
    resident chunk and takes it back, so the loop's carry swaps its
    queue and its in-flight chunk every step and XLA copies each; that is
    nothing for MF-SGD's factor halves and a third of a sweep for LDA's
    4 GB word-topic table.  Here ``step_fn`` is
    ``(carry, model_slice, t, slot) -> (carry, model_slice)``: it gets the
    whole slice, updates slot ``slot`` (a traced index) and nothing else,
    and the loop carries one buffer that never changes place.  Only the
    in-flight chunk is cut out and written back, around its ring hop, and
    with one worker there is no hop and nothing is cut.

    Must be called inside ``shard_map`` (device view).
    """
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    if n_chunks == 1:
        # one chunk is the whole slice: compute, then the hop, which is
        # the unchunked pipeline as it is (it swaps nothing)
        return rotate_pipeline(
            lambda c, x, t: step_fn(c, x, t, 0), carry, model_slice,
            shift=shift, axis=axis, wire=wire)
    wrotate = _wire_rotate(wire, shift, axis)
    n = lax.axis_size(axis)
    if math.gcd(shift % n, n) != 1:
        raise ValueError(
            f"shift={shift} shares a factor with the ring size {n}: chunks "
            "would revisit a worker subset instead of covering the ring")
    for x in jax.tree.leaves(model_slice):
        if x.shape[chunk_axis] % n_chunks:
            raise ValueError(
                f"model slice dim {chunk_axis} of size "
                f"{x.shape[chunk_axis]} does not split into {n_chunks} "
                f"equal rotation chunks")

    def width(x):
        return x.shape[chunk_axis] // n_chunks

    def body(state, t):
        c, buf = state
        if n > 1:
            fly = (t + n_chunks - 1) % n_chunks
            # cut out before the step writes the buffer: the hop has no
            # dependency on this step's compute and overlaps it
            received = wrotate(jax.tree.map(
                lambda x: lax.dynamic_slice_in_dim(
                    x, fly * width(x), width(x), chunk_axis), buf))
        c, buf = step_fn(c, buf, t, t % n_chunks)
        if n > 1:
            buf = jax.tree.map(
                lambda x, r: lax.dynamic_update_slice_in_dim(
                    x, r, fly * width(x), chunk_axis), buf, received)
        return (c, buf), None

    (carry, model_slice), _ = lax.scan(
        body, (carry, model_slice), jnp.arange(n_chunks * n))
    return carry, model_slice


def resident_chunk_index(t, n_chunks: int, *, shift: int = 1,
                         axis: str = WORKER_AXIS):
    """Global index of the chunk this worker computes at step ``t`` of the
    chunked ``rotate_pipeline`` (``n_chunks * num_workers`` steps/epoch).

    Chunk ``p`` of home worker ``w0`` (global index ``n_chunks*w0 + p``)
    computes every ``n_chunks`` steps, moving ``shift`` workers per period;
    the initial in-flight chunk (``p = n_chunks-1``) is one hop ahead.  So
    worker ``w`` at step ``t`` computes chunk
    ``n_chunks * ((w - (t // n_chunks + (r == n_chunks-1)) * shift) % n) + r``
    with ``r = t % n_chunks``.  ``n_chunks=2`` is the historical
    :func:`resident_half_index` schedule; ``n_chunks=1`` degenerates to
    :func:`resident_slice_index`.  The agreement between this formula and
    the pipeline's actual data movement is pinned by
    tests/test_rotate_chunked.py.
    """
    w = lax.axis_index(axis)
    n = lax.axis_size(axis)
    r = t % n_chunks
    ahead = jnp.where(r == n_chunks - 1, 1, 0) if n_chunks > 1 else 0
    home = (w - (t // n_chunks + ahead) * shift) % n
    return n_chunks * home + r


def resident_half_index(t, *, axis: str = WORKER_AXIS):
    """Half-slice resident on this worker at step ``t`` of the pipelined
    two-halves-per-worker rotation — :func:`resident_chunk_index` at
    ``n_chunks=2``, kept as the named schedule MF-SGD and LDA shipped with
    (step t computes half ``2*((w - t//2) % n)`` when t is even and
    ``2*((w - t//2 - 1) % n) + 1`` when odd; after 2n steps both halves
    are home and every (worker, half) pair met exactly once).
    """
    return resident_chunk_index(t, 2, axis=axis)


def resident_slice_index(t, *, shift: int = 1, axis: str = WORKER_AXIS):
    """Global index of the slice resident on this worker at rotation step t
    (unchunked pipeline).

    Slices start at their owners (slice *i* on worker *i*) and move ``shift``
    workers per step, so at step ``t`` worker ``w`` holds slice
    ``(w - t*shift) mod n``.  Apps use this to select the block of local
    data that touches the resident slice (MF-SGD: which rating columns;
    LDA: which vocabulary block).
    """
    n = lax.axis_size(axis)
    return (lax.axis_index(axis) - t * shift) % n
