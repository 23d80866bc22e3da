"""Read-only weight restore for serving: any checkpoint, any mesh.

The serving engine is the first consumer of checkpoints outside the
train loop. It needs exactly the ``.params`` subtree — no optimizer
moments, no RNG, no step counters — restored read-only from whichever
engine wrote the checkpoint (vanilla single file, Orbax sharded
directory, zerostall chunk manifest) and placed for the SERVING mesh,
which almost never matches the training topology.

The path reuses the elastic machinery end to end: the saved manifest +
topology are read without touching tensor data
(``elastic.read_saved_meta``), the params-only reshard plan is computed
and gated by ``elastic.preflight_elastic`` (SC11 infeasible grids, SC05
target-HBM) BEFORE any tensor I/O, and the restore ``device_put``s each
leaf onto its serving placement — replicated on the default device when
no mesh is given, or sharded by the live partition rules on a serving
mesh. Success emits one ``weights_loaded`` event carrying the plan's
accounting; an infeasible plan raises :class:`ServingRestoreError`
naming every finding instead of dying mid-restore.
"""

import time
from pathlib import Path

import numpy as np

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint.elastic import preflight_elastic, read_saved_meta
from pyrecover_tpu.checkpoint.engine import (
    PARAMS_PREFIX,
    CheckpointIntegrityError,
    engine_for_path,
    keystr_parts,
)


class ServingRestoreError(RuntimeError):
    """The checkpoint cannot serve on this topology (preflight findings
    or a params subtree the manifest does not carry)."""


def _params_entries(manifest):
    """Manifest leaves under ``.params``, with their subtree key paths."""
    out = []
    for entry in manifest.get("leaves", []):
        if not entry["path"].startswith(PARAMS_PREFIX):
            continue
        parts = keystr_parts(entry["path"])
        if not parts or parts[0] != "params":
            continue
        out.append((parts[1:], entry))
    if not out:
        raise ServingRestoreError(
            "checkpoint manifest carries no .params leaves — not a "
            "training-state checkpoint this engine can serve from"
        )
    return out


def _read_params_sharded(path):
    """The sharded engine's reader, under the name its tests import."""
    with engine_for_path(path) as engine:
        return engine.read_params(path)


def serving_topology(mesh=None):
    """Topology record of the serving placement (the preflight target)."""
    if mesh is not None:
        from pyrecover_tpu.parallel.mesh import topology_of

        return topology_of(mesh)
    return {"devices": 1, "processes": 1, "mesh": {}}


def serving_target_specs(manifest, mesh):
    """Per-leaf target specs on the serving mesh: the live partition
    rules filtered to the mesh's axes (``spec_for_manifest_path``), or
    fully replicated when serving single-device."""
    from pyrecover_tpu.analysis.shardcheck.manifest import spec_to_json
    from pyrecover_tpu.parallel.mesh import _filter_spec_for_mesh
    from pyrecover_tpu.parallel.sharding import spec_for_manifest_path

    specs = {}
    for entry in manifest.get("leaves", []):
        if not entry["path"].startswith(PARAMS_PREFIX):
            continue
        if mesh is None:
            specs[entry["path"]] = None
            continue
        spec = spec_for_manifest_path(entry["path"], len(entry["shape"]))
        spec = _filter_spec_for_mesh(spec, tuple(mesh.axis_names))
        specs[entry["path"]] = spec_to_json(spec)
    return specs


def load_serving_params(path, model_config, *, mesh=None,  # jaxlint: host-only
                        device_kind=None):
    """Restore the ``.params`` subtree of any checkpoint for serving.

    Returns ``(params, info)`` — ``params`` placed for the serving mesh
    (replicated single-device without one), ``info`` the reshard plan's
    accounting plus the checkpoint step. Raises
    :class:`ServingRestoreError` when the preflight gate rejects the
    plan (indivisible leaf on the serving mesh, target HBM over budget).
    """
    path = Path(path)
    t0 = time.monotonic()
    meta = read_saved_meta(path)
    from pyrecover_tpu.analysis.shardcheck.manifest import (
        manifest_from_ckpt_meta,
    )

    manifest = manifest_from_ckpt_meta(meta)
    entries = _params_entries(manifest)
    params_manifest = {
        "schema": manifest.get("schema", 0),
        "num_leaves": len(entries),
        "leaves": [e for _, e in entries],
    }
    target_topology = serving_topology(mesh)
    findings, plan = preflight_elastic(
        params_manifest, meta.get("topology"), target_topology,
        locus=f"serving:{path.name}", device_kind=device_kind,
        target_specs=serving_target_specs(params_manifest, mesh),
    )
    if findings:
        raise ServingRestoreError(
            f"checkpoint {path.name} cannot serve on "
            f"{target_topology}: "
            + "; ".join(f"{f.rule_id}: {f.message}" for f in findings[:4])
        )

    with engine_for_path(path) as engine, telemetry.span(
        "serving_restore", engine=engine.name, path=str(path),
        metric="serving_restore_s",
    ):
        try:
            host_params = engine.read_params(path)
        except CheckpointIntegrityError as e:
            raise ServingRestoreError(
                f"{e}; refusing to serve from it"
            ) from e
        placed = _place_params(host_params, mesh)
    info = {
        "engine": engine.name, "step": int(meta.get("step", 0)),
        "leaves": len(entries),
        "bytes": int(plan.total_bytes),
        "resharded_leaves": int(plan.resharded_leaves),
        "plan_bytes_moved": int(plan.bytes_moved),
        "seconds": round(time.monotonic() - t0, 4),
    }
    telemetry.emit(
        "weights_loaded", path=str(path),
        target_topology=target_topology, **info,
    )
    return placed, info


def _place_params(host_params, mesh):
    """``device_put`` the host tree onto its serving placement — the
    partition rules under a mesh, the default device otherwise."""
    import jax

    if mesh is None:
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp_readonly(x)), host_params
        )
    from pyrecover_tpu.parallel.sharding import shard_params

    return shard_params(host_params, mesh)


def jnp_readonly(x):
    """Host leaf -> a fresh array safe to place (decouples the result
    from any mmap'd checkpoint read buffer)."""
    return np.ascontiguousarray(x)
