"""Device mesh construction: the TPU-native replacement for the reference's
device ring.

The reference arranges devices in a TCP ring (header -> workers -> tail ->
header, ``Config.java:111-134``) with hand-rolled port arithmetic
(``Communication.java:937-961``).  Here the topology is a
``jax.sharding.Mesh`` with named axes:

- ``dp``: data parallel (concurrent samples — the reference's
  ``core_pool_size`` in-flight pipelining, ``server.py:1003``)
- ``pp``: pipeline stages (the reference's per-device layer ranges)
- ``tp``: tensor parallel (attention heads / MLP columns; absent in the
  reference — SURVEY.md §2.7)
- ``sp``: sequence/context parallel for long sequences (ring attention;
  absent in the reference — SURVEY.md §5.7)

Expert parallelism for MoE rides the ``tp`` axis (experts are sharded over
the same chips that would otherwise shard heads): every rank routes all
tokens, runs the grouped matmul over its local experts' groups, and the
partial sums meet in a ``psum``.

Collectives ride ICI when the mesh maps to a physical slice; across hosts
XLA routes them over DCN.  Axis order is chosen so the innermost (fastest)
mesh dim carries ``tp`` — the axis with the chattiest collectives.
"""

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


AXES = ("dp", "pp", "ep", "sp", "tp")


@dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    pp: int = 1
    ep: int = 1
    tp: int = 1
    sp: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.pp * self.ep * self.tp * self.sp

    def axis_sizes(self) -> dict:
        return {"dp": self.dp, "pp": self.pp, "ep": self.ep,
                "tp": self.tp, "sp": self.sp}


def local_tp_mesh(tp: int):
    """tp mesh over the first ``tp`` local devices, or None for tp <= 1 —
    the one mesh-selection rule shared by the CLI engine builders and the
    worker processes."""
    if tp <= 1:
        return None
    return make_mesh(MeshConfig(tp=tp), jax.devices()[:tp])


def local_sp_mesh(sp: int):
    """sp (sequence/context-parallel) mesh over the first ``sp`` local
    devices, or None for sp <= 1 — the CLI's long-context mesh rule
    (``generate --sp``), mirroring :func:`local_tp_mesh`."""
    if sp <= 1:
        return None
    return make_mesh(MeshConfig(sp=sp), jax.devices()[:sp])


def init_multihost(coordinator: str, num_processes: int, process_id: int,
                   local_device_count: Optional[int] = None) -> None:
    """Join this process to a multi-host JAX runtime (DCN control plane).

    The reference scales across hosts with hand-wired ZMQ sockets and
    port arithmetic (``Communication.java:937-961``); the TPU-native
    equivalent is JAX's distributed runtime: after this call
    ``jax.devices()`` spans every host's chips, ``make_mesh`` builds
    cross-host meshes unchanged, and XLA routes in-mesh collectives over
    ICI within a slice and DCN across slices.  Call before any other JAX
    API touches a backend.  Idempotent-unsafe by JAX design (a second
    call raises) — the CLI invokes it once at startup.
    """
    if num_processes < 1 or not (0 <= process_id < num_processes):
        raise ValueError(
            f"bad process topology: id {process_id} of {num_processes}")
    if local_device_count is not None and local_device_count < 1:
        raise ValueError(
            f"local_device_count must be >= 1, got {local_device_count}")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=(list(range(local_device_count))
                          if local_device_count is not None else None))


def make_mesh(cfg: MeshConfig, devices: Optional[Sequence] = None) -> Mesh:
    """Build the named mesh.  dp is outermost (DCN-friendly: gradient/batch
    collectives are infrequent), tp innermost (ICI-neighbor heavy)."""
    devices = list(devices if devices is not None else jax.devices())
    need = cfg.num_devices
    if len(devices) < need:
        raise ValueError(
            f"mesh {cfg} needs {need} devices, have {len(devices)}")
    # tp innermost: consecutive physical devices are tp-neighbors (the
    # chattiest collectives — per-layer psums — ride adjacent ICI links);
    # sp next (ring-attention ppermute hops one tp-group over), then ep
    # (per-layer all_to_all, chunky but less frequent), then pp, then dp
    # outermost (infrequent gradient/batch collectives, DCN-ok).
    arr = np.asarray(devices[:need]).reshape(cfg.dp, cfg.pp, cfg.ep,
                                             cfg.sp, cfg.tp)
    return Mesh(arr, ("dp", "pp", "ep", "sp", "tp"))
