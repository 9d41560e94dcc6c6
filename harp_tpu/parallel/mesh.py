"""Worker membership and device mesh.

Reference parity (SURVEY.md §3.1): ``edu.iu.harp.worker.Workers`` /
``WorkerInfo`` hold the rank→host:port membership list, the self ID, and the
master flag, populated from a nodes file during ``CollectiveMapper.setup()``'s
socket handshake.  On TPU none of that machinery is needed: membership *is*
the JAX device list, and the handshake is ``jax.distributed.initialize()``
(multi-host) plus mesh construction.  A Harp "worker" maps to one TPU chip
(BASELINE.json north star: "one Harp worker per chip via a pjit mesh").

Two views of the world:

- **Host view** (driver code): :class:`WorkerMesh` wraps a 1-D
  ``jax.sharding.Mesh`` over all chips with axis ``"workers"``; apps use it
  to shard inputs and to ``shard_map`` their step functions.
- **Device view** (inside ``shard_map``): :func:`worker_id`,
  :func:`num_workers`, :func:`is_master` — the SPMD analogues of Harp's
  ``getSelfID()`` / ``getNumWorkers()`` / ``isMaster()``.
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harp_tpu.utils import flightrec, telemetry

WORKER_AXIS = "workers"


def _nbytes(x) -> int:
    """Payload bytes from shape/dtype only (never materializes ``x``)."""
    size = 1
    for s in getattr(x, "shape", np.shape(x)):
        size *= int(s)
    dt = getattr(x, "dtype", None)
    return size * (np.dtype(dt).itemsize if dt is not None
                   else np.result_type(x).itemsize)


_CURRENT_MESH: "WorkerMesh | None" = None


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join a multi-host job (DCN path).  No-op on a single host.

    Replaces Harp's worker bootstrap: the nodes-file discovery + socket
    handshake + membership barrier in ``CollectiveMapper.setup()`` becomes a
    single ``jax.distributed.initialize()`` call; XLA then routes cross-host
    collectives over DCN transparently once the mesh spans hosts.

    Args may be omitted when the standard cluster env vars (e.g. on Cloud
    TPU pods) let JAX auto-detect the topology.
    """
    explicit = coordinator_address is not None or num_processes is not None
    # auto-init only on genuinely multi-host topologies: a coordinator env
    # var, or a TPU hostname list naming more than one worker (single-host
    # TPU VMs export TPU_WORKER_HOSTNAMES=localhost)
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    auto = (
        any(v in os.environ for v in ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS"))
        or "," in hostnames
    )
    if not (explicit or auto):
        return  # single-host: nothing to do
    import jax._src.xla_bridge as xla_bridge

    if xla_bridge.backends_are_initialized():
        coord_set = any(v in os.environ for v in
                        ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS"))
        if explicit or coord_set:
            # a declared multi-host topology that we can no longer join must
            # fail fast — proceeding would run N independent single-host jobs
            raise RuntimeError(
                "init_distributed must run before any JAX computation "
                "(the XLA backend is already initialized) — a coordinator "
                "address is configured, so this process would otherwise "
                "silently run single-host")
        return  # hostname-list heuristic only: assume single-host was intended
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:
        # Double-init is benign; anything else (unreachable coordinator,
        # topology mismatch) must fail fast — swallowing it would leave N
        # hosts running as N independent single-host jobs.
        if "already initialized" not in str(e).lower():
            raise


class WorkerMesh:
    """A 1-D mesh of Harp workers (one worker per chip).

    The Harp equivalents of the main members:

    ==================  =========================================
    harp-tpu            Harp (``edu.iu.harp.worker.Workers``)
    ==================  =========================================
    ``num_workers``     ``getNumWorkers()``
    ``devices``         the nodes list (rank → host:port)
    ``axis``            (implicit: the single worker group)
    ``shard_map(f)``    running ``f`` inside every worker JVM
    ==================  =========================================
    """

    def __init__(self, devices: Sequence[Any] | None = None, axis: str = WORKER_AXIS):
        if devices is None:
            devices = jax.devices()
        self.axis = axis
        self.mesh = Mesh(np.asarray(devices), (axis,))

    # -- membership ---------------------------------------------------------
    @property
    def devices(self):
        return list(self.mesh.devices.flat)

    @property
    def num_workers(self) -> int:
        return self.mesh.devices.size

    # -- sharding helpers ---------------------------------------------------
    def spec(self, dim: int | None = 0, *, ndim: int | None = None) -> P:
        """PartitionSpec with the worker axis on ``dim`` (``None`` = replicated).

        The mesh is 1-D, so exactly one dimension can carry the worker axis.
        """
        if dim is None:
            return P()
        n = (ndim if ndim is not None else dim + 1)
        parts: list[Any] = [None] * n
        parts[dim] = self.axis
        return P(*parts)

    def sharding(self, spec: P | None = None) -> NamedSharding:
        return NamedSharding(self.mesh, spec if spec is not None else self.spec())

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_array(self, x, dim: int | None = 0):
        """Place a host array on the mesh, split along ``dim`` (None = replicate).

        Multi-host note: every process must pass the same GLOBAL ``x``;
        each contributes its addressable shards.  When each host holds
        only its own slice (sharded ingest), use
        :meth:`shard_array_local` instead.
        """
        spec = P() if dim is None else self.spec(dim, ndim=np.ndim(x))
        # flight recorder: shard_array is THE bulk ingest entry point;
        # record_h2d also feeds the same bytes to the memory ledger
        # (memrec, PR 19) as a 'staged' buffer entering the live set
        nbytes = _nbytes(x)
        flightrec.record_h2d(nbytes)
        # the span is the call as the host sees it: device_put returns
        # before the array has arrived
        with telemetry.span("mesh.shard_array", bytes=nbytes):
            return jax.device_put(x, NamedSharding(self.mesh, spec))

    def shard_array_local(self, x_local, global_rows: int | None = None):
        """Assemble a dim-0-sharded global array from PER-PROCESS slices.

        The multi-host ingest primitive (Harp parity: each mapper read
        only its own HDFS split — SURVEY.md §4.2): process p passes only
        the rows its local devices own (the contiguous block
        ``[p * rows_per_process, (p+1) * rows_per_process)`` of the
        global row order), so no host ever materializes — or reads — the
        whole array.  ``global_rows`` defaults to ``local_rows *
        process_count`` (equal splits; required: dim 0 must divide
        evenly over processes).  Single-process: identical to
        ``shard_array(x, 0)``.
        """
        x_local = np.asarray(x_local)
        nproc = jax.process_count()
        gshape = ((global_rows if global_rows is not None
                   else x_local.shape[0] * nproc),) + x_local.shape[1:]
        sh = NamedSharding(self.mesh, self.spec(0, ndim=x_local.ndim))
        flightrec.record_h2d(x_local.nbytes)  # this process's slice only
        if nproc == 1:
            return jax.device_put(x_local, sh)
        return jax.make_array_from_process_local_data(sh, x_local, gshape)

    def survivors(self, lost: int) -> "WorkerMesh":
        """The submesh excluding worker ``lost`` — the elastic shrink
        (PR 15): a permanent worker loss rebuilds execution on this
        mesh instead of killing the job (Harp: YARN retried the whole
        job; here ``harp_tpu.elastic`` replays the repartition plan
        over the survivors from the last checkpoint)."""
        devs = self.devices
        if not 0 <= lost < len(devs):
            raise ValueError(
                f"lost worker {lost} is not on this mesh "
                f"({len(devs)} workers)")
        if len(devs) < 2:
            raise ValueError("cannot shrink a single-worker mesh")
        return WorkerMesh([d for i, d in enumerate(devs) if i != lost],
                          axis=self.axis)

    def shard_map(
        self,
        f: Callable,
        in_specs: Any,
        out_specs: Any,
        check_vma: bool = False,
    ) -> Callable:
        """Wrap ``f`` to run SPMD across workers (the per-worker view).

        This is the moral equivalent of Harp launching ``mapCollective()`` in
        every worker: inside ``f`` each worker sees only its shard, and the
        collective verbs (:mod:`harp_tpu.parallel.collective`) exchange data.
        """
        return jax.shard_map(
            f, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma,
        )

    def __repr__(self) -> str:
        return f"WorkerMesh(num_workers={self.num_workers}, axis={self.axis!r})"


def mesh_2d(n_data: int, n_model: int, devices: Sequence[Any] | None = None,
            axes: tuple[str, str] = (WORKER_AXIS, "model")) -> Mesh:
    """A 2-D (data × model) ``jax.sharding.Mesh`` — the tensor-parallel
    extension beyond Harp's single worker axis (SURVEY.md §3.5: TP is not
    in the reference; this exists so model-sharded layers can ride GSPMD
    sharding annotations with no explicit collectives).
    """
    if devices is None:
        devices = jax.devices()
    if n_data * n_model > len(devices):
        raise ValueError(
            f"mesh_2d({n_data}x{n_model}) needs {n_data * n_model} devices, "
            f"have {len(devices)}")
    grid = np.asarray(devices[: n_data * n_model]).reshape(n_data, n_model)
    return Mesh(grid, axes)


def current_mesh() -> WorkerMesh:
    """The process-wide default mesh (created over all devices on first use)."""
    global _CURRENT_MESH
    if _CURRENT_MESH is None:
        _CURRENT_MESH = WorkerMesh()
    return _CURRENT_MESH


def set_mesh(mesh: WorkerMesh | None) -> None:
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


@contextlib.contextmanager
def use_mesh(mesh: WorkerMesh):
    global _CURRENT_MESH
    prev, _CURRENT_MESH = _CURRENT_MESH, mesh
    try:
        yield mesh
    finally:
        _CURRENT_MESH = prev


# -- device view (valid only inside shard_map) ------------------------------

def worker_id(axis: str = WORKER_AXIS):
    """This worker's rank — Harp's ``getSelfID()`` (device view)."""
    return lax.axis_index(axis)


def num_workers(axis: str = WORKER_AXIS):
    """Worker count — Harp's ``getNumWorkers()`` (device view)."""
    return lax.axis_size(axis)


def is_master(axis: str = WORKER_AXIS):
    """True on rank 0 — Harp's ``isMaster()`` (device view)."""
    return lax.axis_index(axis) == 0
