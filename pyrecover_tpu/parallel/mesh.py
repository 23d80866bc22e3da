"""Device-mesh construction and activation sharding constraints.

This is the TPU-native replacement for the reference's NCCL/DDP runtime
(`dist_utils.py:38-68`: SLURM env discovery → `init_process_group("nccl")` →
`torch.cuda.set_device`). On TPU there is no rendezvous code to write: the
slice topology comes from the TPU runtime via `jax.distributed.initialize()`,
and all communication is XLA collectives over ICI/DCN inserted by the
compiler from sharding annotations.

Mesh axes:
  * ``data``     — data parallelism (batch dimension). DDP's gradient
                   allreduce (reference `train.py:268-269`) becomes an XLA
                   AllReduce over this axis, inserted automatically by jit.
  * ``fsdp``     — parameter/optimizer sharding (ZeRO-3 style). The reference
                   has no FSDP (SURVEY §2.2) — this axis is the TPU-idiomatic
                   way to fit models that don't fit replicated.
  * ``tensor``   — tensor (Megatron-style) parallelism over heads / FFN
                   hidden, collectives ride ICI.
  * ``sequence`` — sequence/context parallelism for long sequences (ring
                   attention over this axis).
  * ``pipeline`` — pipeline parallelism over transformer layers: the stacked
                   layer pytree is sharded on its leading (layer) axis, and
                   microbatch activations rotate stage→stage via
                   ``ppermute`` inside a ``shard_map`` schedule
                   (`parallel.pipeline`). The reference has no PP
                   (SURVEY §2.2).
  * ``expert``   — expert parallelism for MoE layers: expert-stacked FFN
                   weights are sharded on their expert axis and token
                   dispatch/combine einsums become all-to-alls over this
                   axis (models/moe.py). The reference is dense-only
                   (SURVEY §2.2).
"""

import contextlib
import dataclasses
import os

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "tensor"
AXIS_SEQ = "sequence"
AXIS_PIPE = "pipeline"
AXIS_EXPERT = "expert"

MESH_AXES = (AXIS_PIPE, AXIS_DATA, AXIS_FSDP, AXIS_TENSOR, AXIS_SEQ, AXIS_EXPERT)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. ``data=-1`` means "all remaining devices".

    The pipeline axis is outermost in device order: stage boundaries are the
    lowest-bandwidth cut (only activations cross them, once per microbatch
    tick), so they should land on the outermost/slowest links.
    """

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    sequence: int = 1
    pipeline: int = 1
    expert: int = 1

    def resolve(self, n_devices):
        fixed = (
            self.fsdp * self.tensor * self.sequence * self.pipeline * self.expert
        )
        data = self.data
        if data == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by "
                    f"pipeline*fsdp*tensor*sequence*expert={fixed}"
                )
            data = n_devices // fixed
        total = data * fixed
        if total != n_devices:
            raise ValueError(
                f"Mesh pp{self.pipeline}xdp{data}xfsdp{self.fsdp}"
                f"xtp{self.tensor}xsp{self.sequence}xep{self.expert}={total} "
                f"!= available devices {n_devices}"
            )
        return (
            self.pipeline, data, self.fsdp, self.tensor, self.sequence,
            self.expert,
        )


def create_mesh(config=None, devices=None):
    """Build the 6-axis ``jax.sharding.Mesh`` over the available devices.

    Physical placement is topology-aware, not a flat reshape:

      * Single slice: ``mesh_utils.create_device_mesh`` maps the logical
        mesh onto the ICI torus so that the innermost logical axes land on
        physically adjacent chips (wraparound links used where available).
      * Multi-slice (DCN-connected): ``create_hybrid_device_mesh`` keeps
        every model axis inside a slice and splits the DATA axis across
        slices — gradient allreduce is the only per-step DCN traffic, which
        is the standard TPU multislice recipe (scaling-book). Requires
        ``data`` divisible by the slice count.

    Only virtual CPU devices (tests, dry runs) have no topology to map and
    take a plain reshape. On an accelerator a mapping failure RAISES: a
    flat device order would put inner axes across the slowest links and
    the job would "work" at a fraction of its speed.
    """
    if config is None:
        config = MeshConfig()
    if devices is None:
        devices = jax.devices()
    shape = config.resolve(len(devices))

    n_slices = len({getattr(d, "slice_index", 0) for d in devices})
    if n_slices > 1:
        data_idx = MESH_AXES.index(AXIS_DATA)
        if shape[data_idx] % n_slices != 0:
            # fail fast: a flat device order would span model axes
            # across DCN and the job would "work" at a fraction of the speed
            raise ValueError(
                f"data axis {shape[data_idx]} not divisible by "
                f"{n_slices} DCN-connected slices; set --dp to a multiple "
                "of the slice count so only gradient allreduce crosses DCN"
            )
        from jax.experimental import mesh_utils

        per_slice = list(shape)
        per_slice[data_idx] //= n_slices
        dcn = [1] * len(shape)
        dcn[data_idx] = n_slices
        dev_array = mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn, devices=devices, allow_split_physical_axes=True
        )
        return Mesh(dev_array, MESH_AXES)
    if all(d.platform == "cpu" for d in devices):
        return Mesh(np.asarray(devices).reshape(shape), MESH_AXES)
    from jax.experimental import mesh_utils

    dev_array = mesh_utils.create_device_mesh(
        shape, devices=devices, allow_split_physical_axes=True
    )
    return Mesh(dev_array, MESH_AXES)


def mesh_axis_size(mesh, axis):
    return mesh.shape.get(axis, 1)


def mesh_shape_dict(mesh):
    """Plain ``{axis: size}`` dict of a mesh's logical shape (JSON-ready)."""
    return {str(k): int(v) for k, v in dict(mesh.shape).items()}


def topology_of(mesh):
    """JSON-ready record of the topology a mesh spans: device count,
    process count, and the logical mesh shape. Saved into every
    checkpoint's metadata so an elastic resume can diff the saved
    topology against the live one without reading any tensor data."""
    return {
        "devices": int(np.asarray(mesh.devices).size),
        "processes": int(jax.process_count()),
        "mesh": mesh_shape_dict(mesh),
    }


def state_topology(state):
    """Topology spanned by a live state pytree: the mesh carried by the
    first NamedSharding leaf, else the device span of the first jax.Array
    (host/numpy-only trees report the process's device view). This is how
    the checkpoint engines record topology without being handed a mesh."""
    from jax.sharding import NamedSharding

    for leaf in jax.tree_util.tree_leaves(state):
        sharding = getattr(leaf, "sharding", None)
        if isinstance(sharding, NamedSharding):
            return topology_of(sharding.mesh)
    for leaf in jax.tree_util.tree_leaves(state):
        device_set = getattr(getattr(leaf, "sharding", None), "device_set", None)
        if device_set:
            return {
                "devices": len(device_set),
                "processes": int(jax.process_count()),
                "mesh": None,
            }
    return {
        "devices": int(jax.device_count()),
        "processes": int(jax.process_count()),
        "mesh": None,
    }


_dropped_axes_warned = set()


def _note_dropped_axis(axis, axis_names):  # obscheck: once
    """A spec named an axis the mesh does not have AT ALL (not a manual
    axis being filtered — those are deliberate): the dimension will be
    silently replicated, which is exactly how a typo'd or stale axis name
    turns into a 6× memory regression. Warn + emit telemetry once per
    axis name per process so the regression is visible without spamming
    every trace."""
    if axis in _dropped_axes_warned:
        return
    # concur: disable-next=unguarded-shared-state -- benign race: an
    # idempotent warn-once cache (set.add of the same key); two roots
    # racing (train main vs the hot-swap watcher's spec filtering) at
    # worst emit the once-per-axis warning twice
    _dropped_axes_warned.add(axis)
    from pyrecover_tpu import telemetry
    from pyrecover_tpu.utils.logging import log_host0

    log_host0(
        "sharding spec names axis %r which is absent from the mesh axes "
        "%s; the axis is DROPPED and that dimension replicated — if this "
        "is not a deliberately partial mesh, fix the spec (shardcheck "
        "flags this as SC02)", axis, tuple(axis_names),
        level=30,  # WARNING
    )
    telemetry.emit(
        "spec_axis_dropped", axis=str(axis), mesh_axes=list(axis_names)
    )


def _filter_spec_for_mesh(spec, axis_names, all_axis_names=None):
    """Drop mesh axes that don't exist (size-1 axes are fine; missing names
    would error), so model code can annotate with the full logical spec and
    degrade gracefully on smaller meshes. ``all_axis_names``, when given,
    is the mesh's FULL axis set: an axis absent from it (as opposed to
    one filtered because it is manually bound by an enclosing shard_map)
    is warned about once per process — silent drops are how replication
    regressions hide."""
    out = []

    def keep(a):
        if a in axis_names:
            return True
        if all_axis_names is not None and a not in all_axis_names:
            _note_dropped_axis(a, all_axis_names)
        return False

    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if keep(a))
            out.append(kept if kept else None)
        else:
            out.append(entry if keep(entry) else None)
    return P(*out)


def nonmanual_axes(mesh):
    """Mesh axis names NOT currently bound manually (i.e. usable in sharding
    constraints). Inside a ``shard_map`` the manual axes are implicit — a
    constraint naming them would error."""
    types = getattr(mesh, "axis_types", None)
    if types is None:
        return set(mesh.axis_names)
    from jax.sharding import AxisType

    return {
        n for n, t in zip(mesh.axis_names, types) if t != AxisType.Manual
    }


_CONSTRAINTS_DISABLED = False


@contextlib.contextmanager
def constraints_disabled():
    """Trace-time switch making ``constrain`` a no-op.

    The 1F1B pipeline schedule (parallel/pipeline.py) runs model code
    inside ``lax.cond`` branches whose predicate VARIES by pipeline stage.
    A ``with_sharding_constraint`` there can make GSPMD insert reshard
    collectives inside the branch — a collective only some stages execute,
    which deadlocks the mesh (observed with the MoE dispatch constrains).
    Inside that region the constraints are disabled and sharding
    propagation from the (already-sharded) inputs carries the layouts.
    """
    global _CONSTRAINTS_DISABLED
    prev = _CONSTRAINTS_DISABLED
    _CONSTRAINTS_DISABLED = True
    try:
        yield
    finally:
        _CONSTRAINTS_DISABLED = prev


def constrain(x, *spec):
    """``with_sharding_constraint`` that is a no-op outside a mesh context.

    Model code calls ``constrain(x, 'data', None, 'tensor')`` unconditionally;
    under ``jax.sharding.set_mesh`` (or an in-scope concrete mesh) the
    constraint is applied, otherwise the value passes through untouched so
    the same model runs single-device. Axes that are missing from the mesh
    OR manually bound by an enclosing ``shard_map`` are dropped from the
    spec, so the same model code also runs inside manual regions (and
    ``constraints_disabled`` regions skip the constraint entirely).
    """
    if _CONSTRAINTS_DISABLED:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    filtered = _filter_spec_for_mesh(
        spec, nonmanual_axes(mesh), all_axis_names=set(mesh.axis_names)
    )
    return jax.lax.with_sharding_constraint(x, filtered)


def initialize_distributed(coordinator_address=None, num_processes=None,
                           process_id=None, required=False):
    """Multi-host init: the TPU-native `maybe_init_distributed`
    (reference `dist_utils.py:38-68`).

    On Cloud TPU pods all arguments are discovered from the TPU metadata/
    runtime, so a bare ``jax.distributed.initialize()`` suffices; explicit
    args are accepted for non-TPU clusters (the SLURM-env analogue).
    No-op when running single-process.

    Failure policy (reference `dist_utils.py:64-65` exits hard when
    ``--distributed`` is set without a usable env): once a cluster env is
    detected — or ``required=True`` — a failed rendezvous RAISES. Falling
    back to single-process silently would have every pod host train a
    divergent solo run and clobber each other's checkpoints.
    """
    # IMPORTANT: don't touch jax.devices()/process_count() here — that would
    # initialize the local backend and make distributed init impossible.
    if jax.distributed.is_initialized():
        return  # already initialized (e.g. by a launcher/test harness)
    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    else:
        # auto-init only when a multi-host cluster is actually detectable:
        # an explicit coordinator, or a TPU worker list naming >1 host.
        # Anything else is a plain single-process run (the reference's
        # maybe_init_distributed no-op path, dist_utils.py:60-68).
        coord = os.environ.get("COORDINATOR_ADDRESS") or os.environ.get(
            "JAX_COORDINATOR_ADDRESS"
        )
        workers = [
            w for w in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if w
        ]
        if not coord and len(workers) <= 1:
            if required:
                raise RuntimeError(
                    "--distributed requested but no cluster environment "
                    "found: set COORDINATOR_ADDRESS/JAX_COORDINATOR_ADDRESS "
                    "or run under a TPU pod runtime (TPU_WORKER_HOSTNAMES). "
                    "Refusing to fall back to single-process (reference "
                    "dist_utils.py:64-65)."
                )
            return
    try:
        jax.distributed.initialize(**kwargs)
        # events emitted before the rendezvous were stamped host 0 from a
        # pre-init backend; drop that cache so the next emit re-resolves
        from pyrecover_tpu.telemetry import bus as _telemetry_bus

        _telemetry_bus.reset_process_index()
    except (ValueError, RuntimeError) as e:
        # A cluster env WAS detected (or explicitly given): failing half-way
        # must stop the job, not degrade it to N divergent solo runs.
        raise RuntimeError(
            f"distributed rendezvous failed ({e}); refusing to continue "
            "single-process with a cluster environment present"
        ) from e


def sync_global_devices(tag="barrier"):
    """Cross-host barrier (reference `dist.barrier()` call sites, e.g.
    checkpoint.py:56,103). No-op single-process. Bounded: the wait runs
    inside a ``collective_phase`` so a host that never arrives becomes a
    named ``distributed_wait_timeout`` + flight bundle, not silence."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        from pyrecover_tpu import telemetry

        with telemetry.collective_phase(f"barrier:{tag}"):
            multihost_utils.sync_global_devices(tag)


def broadcast_host0_scalar(value):
    """Host-0 decides, everyone follows — the stop-flag broadcast pattern
    (reference `train.py:342-346`). Returns the host-0 value on all hosts.
    This is the SANCTIONED laundering point for host-divergent state:
    distcheck (DC03/DC06) treats a value that passed through here as
    congruent across hosts."""
    if jax.process_count() <= 1:
        return value
    from jax.experimental import multihost_utils

    from pyrecover_tpu import telemetry

    arr = np.asarray(value)
    with telemetry.collective_phase("broadcast_host0_scalar"):
        return multihost_utils.broadcast_one_to_all(arr).item()


def broadcast_host0_obj(obj):
    """Host-0 decides a STRUCTURED value (a candidate list, a manifest
    doc), everyone follows. JSON round-trip, so the payload must be
    JSON-serializable; identity single-process.

    Two legs because hosts must NOT need to agree on the payload size up
    front (that agreement is exactly what's being established): the byte
    length is broadcast first, then every peer supplies a placeholder
    buffer of that exact size for the payload broadcast. This is how
    ``_resume`` pins every host to the SAME checkpoint-candidate walk
    even when per-host filesystem listings disagree transiently."""
    if jax.process_count() <= 1:
        return obj
    import json as _json

    from jax.experimental import multihost_utils

    from pyrecover_tpu import telemetry

    payload = np.frombuffer(
        _json.dumps(obj).encode("utf-8"), dtype=np.uint8
    )
    with telemetry.collective_phase("broadcast_host0_obj"):
        n = int(multihost_utils.broadcast_one_to_all(
            np.asarray(payload.size, dtype=np.int64)
        ))
        buf = payload if payload.size == n else np.zeros(n, dtype=np.uint8)
        data = np.asarray(multihost_utils.broadcast_one_to_all(buf))
    return _json.loads(bytes(data).decode("utf-8"))
