"""Device-mesh management for SPMD parallelism.

No reference analog (the reference's parallelism is PS/NCCL data-parallel
only, SURVEY.md §2.3 "absent" list) — this module is the foundation the TPU
build adds: a global ``jax.sharding.Mesh`` with named axes (``dp``, ``fsdp``,
``tp``, ``sp``, ``ep``...) that KVStore, Trainer, and the model zoo's
sharding rules all reference.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence

import numpy as _onp

from ..base import MXNetError

_state = threading.local()


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the replication check — one spelling,
    shared by the trainer, pipeline and ring-attention modules."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape: Dict[str, int] = None, devices=None):
    """Create a Mesh from an axis-name->size dict, e.g. {'dp': 2, 'tp': 4}."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if shape is None:
        shape = {"dp": len(devices)}
    sizes = list(shape.values())
    total = int(_onp.prod(sizes))
    if total > len(devices):
        raise MXNetError(
            f"mesh shape {shape} needs {total} devices, have {len(devices)}")
    arr = _onp.asarray(devices[:total]).reshape(sizes)
    return Mesh(arr, tuple(shape.keys()))


def set_mesh(mesh):
    _state.mesh = mesh
    return mesh


def get_mesh(create=False):
    mesh = getattr(_state, "mesh", None)
    if mesh is None and create:
        import jax

        if len(jax.devices()) >= 1:
            mesh = make_mesh({"dp": len(jax.devices())})
            _state.mesh = mesh
    return mesh


def shrink_mesh(mesh, lost, axis="dp", power_of_two=True):
    """Rebuild ``mesh`` without the ``lost`` index(es) along ``axis`` —
    the elastic-restart primitive (``resilience.elastic``): a chip loss
    takes its whole slice of the named axis (its ICI ring segment), and
    the surviving devices form a smaller mesh of the same axis names.

    ``power_of_two=True`` (default) additionally truncates the surviving
    axis to the largest power of two — collectives on TPU meshes are
    ring-scheduled over power-of-two groups, and dp8→dp4 keeps per-shape
    executables reusable where dp7 would not. Returns the new Mesh (the
    caller decides whether to :func:`set_mesh` it).

    Only data-parallel-like axes (``dp``/``fsdp``) can shrink: dropping a
    slice of a model-parallel axis would change every sharded parameter's
    shape, so that raises :class:`~..resilience.elastic.MeshDegraded`
    naming the unsupported axis. Likewise a non-power-of-two survivor
    count on a *composite* (multi-axis) mesh is rejected even with
    ``power_of_two=False`` — the other axes' ring schedules assume
    power-of-two groups (a single-axis dp mesh may shrink to any size;
    regression-pinned dp8→dp7).
    """
    from jax.sharding import Mesh

    if axis not in mesh.axis_names:
        raise MXNetError(
            f"shrink_mesh: axis {axis!r} not in mesh axes {mesh.axis_names}")
    lost = sorted({int(i) for i in (lost if hasattr(lost, "__iter__")
                                    else [lost])})
    if axis not in ("dp", "fsdp"):
        from ..resilience.elastic import MeshDegraded

        raise MeshDegraded(
            f"shrink_mesh: axis {axis!r} is not a data-parallel axis — "
            "dropping a slice of a model-parallel axis would change every "
            "sharded parameter's shape; only 'dp'/'fsdp' replicas can be "
            "dropped elastically", lost_replicas=lost,
            mesh_size=int(mesh.devices.size))
    ax = mesh.axis_names.index(axis)
    size = mesh.devices.shape[ax]
    bad = [i for i in lost if not 0 <= i < size]
    if bad:
        raise MXNetError(
            f"shrink_mesh: lost indices {bad} out of range for axis "
            f"{axis!r} of size {size}")
    keep = [i for i in range(size) if i not in lost]
    if not power_of_two and len(mesh.axis_names) > 1 \
            and len(keep) > 1 and (len(keep) & (len(keep) - 1)):
        from ..resilience.elastic import MeshDegraded

        raise MeshDegraded(
            f"shrink_mesh: axis {axis!r} would survive with {len(keep)} "
            "slots — not a power of two. On a composite mesh "
            f"{dict(zip(mesh.axis_names, mesh.devices.shape))} the other "
            "axes' ring schedules assume power-of-two groups; use "
            "power_of_two=True to truncate, or rebuild the mesh",
            lost_replicas=lost, mesh_size=int(mesh.devices.size))
    if power_of_two and len(keep) > 1:
        target = 1 << (len(keep).bit_length() - 1)
        keep = keep[:target]
    if not keep:
        raise MXNetError(
            f"shrink_mesh: no surviving devices on axis {axis!r} "
            f"(lost {lost} of {size})")
    arr = _onp.take(mesh.devices, keep, axis=ax)
    return Mesh(arr, mesh.axis_names)


def touched_groups(mesh, lost_devices, axis="dp"):
    """Map arbitrary lost-device addresses to the set of ``axis`` indices
    (dp-groups) they touch. Each entry of ``lost_devices`` is either a flat
    device index into ``mesh.devices`` (C order) or a coordinate dict
    ``{"axis": name, "index": i}`` addressing a whole slice of a named
    axis. Addressing a slice of a *different* axis touches every
    ``axis``-group (the slice crosses all of them)."""
    names = mesh.axis_names
    if axis not in names:
        raise MXNetError(
            f"touched_groups: axis {axis!r} not in mesh axes {names}")
    ax = names.index(axis)
    shape = mesh.devices.shape
    if isinstance(lost_devices, (int, dict)):
        lost_devices = [lost_devices]
    touched = set()
    for dev in lost_devices:
        if isinstance(dev, dict):
            a = dev.get("axis")
            if a not in names:
                raise MXNetError(
                    f"touched_groups: lost-device axis {a!r} not in mesh "
                    f"axes {names}")
            i = int(dev.get("index", 0))
            extent = shape[names.index(a)]
            if not 0 <= i < extent:
                raise MXNetError(
                    f"touched_groups: lost-device index {i} out of range "
                    f"for axis {a!r} of size {extent}")
            if a == axis:
                touched.add(i)
            else:
                # a whole slice of another axis crosses every dp-group
                touched.update(range(shape[ax]))
        else:
            f = int(dev)
            if not 0 <= f < mesh.devices.size:
                raise MXNetError(
                    f"touched_groups: flat device index {f} out of range "
                    f"for mesh of size {mesh.devices.size}")
            coords = _onp.unravel_index(f, shape)
            touched.add(int(coords[ax]))
    return touched


def rebuild_mesh(mesh, lost_devices, axis="dp", power_of_two=True):
    """Composed-mesh elasticity policy: given arbitrary lost device
    coordinates on a (possibly multi-axis) mesh, keep every non-``axis``
    extent (tp/pp) fixed and drop each ``axis``-group (dp-group) touched
    by a loss. A chip loss anywhere in a dp-group breaks that group's ICI
    rings, so the whole group leaves the mesh; the tp/pp structure of the
    survivors is untouched and their sharded parameters keep their shapes.

    ``lost_devices`` entries are flat device indices or coordinate dicts
    ``{"axis": ..., "index": ...}`` (see :func:`touched_groups` —
    coordinate-addressed ``chip_loss`` faults arrive in either form). On a
    composite mesh the survivor count is truncated to the largest power of
    two (ring schedules on the remaining axes assume power-of-two groups);
    a single-axis mesh honors the existing any-size exception when
    ``power_of_two=False``, exactly like :func:`shrink_mesh`.

    Compositions that shard over expert (``ep``, :mod:`.moe`) or sequence
    (``sp``, :mod:`.ring_attention`) axes are pinned *unsupported*: a
    dp-group drop cannot preserve their all-to-all / ring layouts, so the
    loss raises :class:`~..resilience.elastic.MeshDegraded` loudly (with
    ``lost_replicas``/``mesh_size`` populated) instead of silently
    misplacing shards.

    Returns ``(new_mesh, group_map)`` where ``group_map`` maps each
    surviving old dp-group index to its index on the new mesh.
    """
    from jax.sharding import Mesh

    from ..resilience.elastic import MeshDegraded

    names = mesh.axis_names
    if axis not in names:
        raise MXNetError(
            f"rebuild_mesh: axis {axis!r} not in mesh axes {names}")
    ax = names.index(axis)
    size = mesh.devices.shape[ax]
    touched = touched_groups(mesh, lost_devices, axis=axis)
    unsupported = [a for a in names if a in ("ep", "sp")]
    if unsupported and touched:
        raise MeshDegraded(
            f"rebuild_mesh: mesh axes {unsupported} are pinned unsupported "
            "under mesh loss — dropping a dp-group cannot preserve the "
            "MoE all-to-all ('ep') / ring-attention ('sp') layouts; "
            "restart on a fresh mesh instead",
            lost_replicas=sorted(touched), mesh_size=int(mesh.devices.size))
    keep = [i for i in range(size) if i not in touched]
    if not keep:
        raise MeshDegraded(
            f"rebuild_mesh: the loss touches every {axis!r}-group "
            f"(lost {sorted(touched)} of {size}) — no survivor mesh",
            lost_replicas=sorted(touched), mesh_size=int(mesh.devices.size))
    composite = len(names) > 1
    if composite and (len(keep) & (len(keep) - 1)):
        if not power_of_two:
            raise MeshDegraded(
                f"rebuild_mesh: axis {axis!r} would survive with "
                f"{len(keep)} groups — not a power of two. On a composite "
                f"mesh {dict(zip(names, mesh.devices.shape))} the other "
                "axes' ring schedules assume power-of-two groups",
                lost_replicas=sorted(touched),
                mesh_size=int(mesh.devices.size))
        keep = keep[:1 << (len(keep).bit_length() - 1)]
    elif power_of_two and len(keep) > 1:
        keep = keep[:1 << (len(keep).bit_length() - 1)]
    arr = _onp.take(mesh.devices, keep, axis=ax)
    group_map = {int(old): new for new, old in enumerate(keep)}
    return Mesh(arr, names), group_map


def mesh_contexts(mesh, axis="dp", full=False):
    """The :class:`~..device.Context` list matching ``mesh``'s slots along
    ``axis`` (one context per axis index, resolved via the device at the
    zero position of every other axis) — what a data-parallel training
    loop initializes parameter replicas on.

    On a composed mesh each ``axis``-group spans the whole cross-section
    of the other axes; ``full=True`` returns one context *list* per group
    (every device in the group's slice, C order) instead of just the
    zero-position representative — what composed-mesh elasticity uses to
    attribute a lost chip to its dp-group."""
    from ..device import from_jax_device

    if axis not in mesh.axis_names:
        raise MXNetError(
            f"mesh_contexts: axis {axis!r} not in {mesh.axis_names}")
    ax = mesh.axis_names.index(axis)
    if full:
        groups = _onp.moveaxis(mesh.devices, ax, 0)
        return [[from_jax_device(d) for d in grp.ravel()] for grp in groups]
    sel = [0] * mesh.devices.ndim
    out = []
    for i in range(mesh.devices.shape[ax]):
        sel[ax] = i
        out.append(from_jax_device(mesh.devices[tuple(sel)]))
    return out


class mesh_scope:
    """``with mesh_scope({'dp': 4, 'tp': 2}):`` — set + restore global mesh."""

    def __init__(self, shape_or_mesh):
        from jax.sharding import Mesh

        if isinstance(shape_or_mesh, Mesh):
            self._mesh = shape_or_mesh
        else:
            self._mesh = make_mesh(shape_or_mesh)
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_state, "mesh", None)
        _state.mesh = self._mesh
        return self._mesh

    def __exit__(self, *exc):
        _state.mesh = self._prev
        return False


def initialize_distributed(coordinator_address=None, num_processes=None,
                           process_id=None):
    """Multi-host init (reference: ps-lite scheduler env / dmlc tracker).

    Maps ``DMLC_*``-style launch to ``jax.distributed.initialize``: no
    scheduler/server roles — every process is a worker (SPMD
    multi-controller, SURVEY.md §7 translation table).

    Arguments left ``None`` are read from the environment the
    ``tools/launch.py`` launcher sets (``MXNET_TPU_COORDINATOR``,
    ``MXNET_TPU_NUM_PROCS``, ``MXNET_TPU_PROC_ID``) with the reference's
    ``DMLC_PS_ROOT_URI``/``DMLC_PS_ROOT_PORT``/``DMLC_NUM_WORKER``/
    ``DMLC_WORKER_ID`` accepted as aliases (`tools/launch.py:67-72`,
    `distributed_training.md:262`).
    """
    import os

    import jax

    env = os.environ
    if coordinator_address is None:
        coordinator_address = env.get("MXNET_TPU_COORDINATOR")
        if coordinator_address is None and "DMLC_PS_ROOT_URI" in env:
            coordinator_address = (env["DMLC_PS_ROOT_URI"] + ":" +
                                   env.get("DMLC_PS_ROOT_PORT", "9091"))
    if num_processes is None:
        v = env.get("MXNET_TPU_NUM_PROCS", env.get("DMLC_NUM_WORKER"))
        num_processes = int(v) if v is not None else None
    if process_id is None:
        v = env.get("MXNET_TPU_PROC_ID", env.get("DMLC_WORKER_ID"))
        process_id = int(v) if v is not None else None

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
