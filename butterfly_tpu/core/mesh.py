"""Device mesh bringup.

TPU-native replacement for the reference's planned "communication layer"
bootstrap (/root/reference/CLAUDE.md:20): instead of NCCL communicator
setup, we build a `jax.sharding.Mesh` whose axis order maps parallelism
kinds onto the ICI topology — `tensor` innermost (fastest links, all-reduce
every layer), `data` outermost (least traffic, may cross DCN).
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from butterfly_tpu.core.config import MESH_AXES, MeshConfig


def make_mesh(cfg: MeshConfig, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh with the canonical axes (data, stage, expert, seq, tensor).

    Axis sizes of 1 are kept (not squeezed) so PartitionSpecs can always
    name every axis; XLA elides collectives over size-1 axes for free.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if cfg.num_devices != n:
        raise ValueError(
            f"MeshConfig wants {cfg.num_devices} devices "
            f"({dict(zip(MESH_AXES, cfg.axis_sizes))}) but {n} are available"
        )
    dev_array = np.asarray(devices).reshape(cfg.axis_sizes)
    return Mesh(dev_array, MESH_AXES)


def local_mesh() -> Mesh:
    """Single-device mesh (all axes size 1) — the CPU/1-chip dev loop."""
    return make_mesh(MeshConfig(), devices=jax.devices()[:1])


def mesh_for(n_devices: int, tensor: int = 0, stage: int = 1, expert: int = 1,
             seq: int = 1) -> Mesh:
    """Convenience: fill `tensor` (or `data`) to consume n_devices."""
    if tensor == 0:
        tensor = n_devices // (stage * expert * seq)
    data = n_devices // (stage * expert * seq * tensor)
    cfg = MeshConfig(data=data, stage=stage, expert=expert, seq=seq, tensor=tensor)
    return make_mesh(cfg, devices=jax.devices()[:n_devices])


def slice_groups(devices: Sequence[jax.Device]) -> dict:
    """Group devices by TPU slice (DCN island). Devices without a
    slice_index (CPU, single-slice) all land in slice 0."""
    groups: dict = {}
    for d in devices:
        groups.setdefault(getattr(d, "slice_index", 0), []).append(d)
    return groups


def make_hybrid_mesh(cfg: MeshConfig,
                     devices: Optional[Sequence[jax.Device]] = None,
                     dcn_axes: Sequence[str] = ("data",)) -> Mesh:
    """Mesh for multi-slice deployments: `dcn_axes` span slices (over
    DCN), every other axis stays inside one slice (over ICI).

    The scaling-book recipe: collectives that run every layer (tensor,
    expert, seq all-reduces / all-to-alls) must ride ICI, so only the
    low-traffic axes — `data` by default, optionally `stage` whose
    ppermute handoff crosses a slice boundary once per microbatch — may
    be placed across slices. Single-slice (or CPU) device sets fall
    back to the plain ICI mesh, so callers can use this unconditionally.
    """
    if devices is None:
        devices = jax.devices()
    groups = slice_groups(devices)
    if len(groups) == 1:
        return make_mesh(cfg, devices)

    sizes = dict(zip(MESH_AXES, cfg.axis_sizes))
    bad = [a for a in dcn_axes if a not in MESH_AXES]
    if bad:
        raise ValueError(f"unknown mesh axes {bad}")
    dcn_shape = [sizes[a] if a in dcn_axes else 1 for a in MESH_AXES]
    ici_shape = [1 if a in dcn_axes else sizes[a] for a in MESH_AXES]
    n_dcn = int(np.prod(dcn_shape))
    per_slice = int(np.prod(ici_shape))
    if n_dcn != len(groups):
        raise ValueError(
            f"dcn axes {tuple(dcn_axes)} have total size {n_dcn} but the "
            f"job spans {len(groups)} slices")
    if any(len(g) != per_slice for g in groups.values()):
        raise ValueError(
            f"each slice must contribute {per_slice} devices "
            f"(got {[len(g) for g in groups.values()]})")
    from jax.experimental import mesh_utils
    dev_array = mesh_utils.create_hybrid_device_mesh(
        ici_shape, dcn_shape, devices=devices,
        allow_split_physical_axes=True)
    return Mesh(dev_array, MESH_AXES)


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host control-plane bringup (SURVEY.md §3 call stack 3).

    On a real pod each host calls this before `make_mesh`; jax.distributed
    handles the DCN rendezvous that NCCL/MPI would in a GPU design. No-op
    when single-process (the common dev/test case).
    """
    if num_processes is None:
        num_processes = int(os.environ.get("BUTTERFLY_NUM_PROCESSES", "1"))
    if num_processes <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def device_report() -> dict:
    """The devices as JAX reports them: what every entry point prints
    so that a result names what it ran on."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def mesh_ctx(mesh: Optional[Mesh]):
    """`with` context making `mesh` the ambient mesh for jit dispatch
    and for the mesh-aware call sites that read it while tracing (kernel
    wrappers, EP dispatch); a no-op for an unmeshed engine (None)."""
    return contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
