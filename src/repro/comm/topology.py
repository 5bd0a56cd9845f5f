"""Pod topology helpers: the TPU analogue of the paper's node hierarchy.

A :class:`PodTopology` describes a machine as ``npods`` pods of ``ppn`` chips
(the paper's nodes of PPN processes).  World rank ``r`` lives on pod
``r // ppn`` with pod-local rank ``r % ppn``; this matches the mesh built by
:func:`make_exchange_mesh`, which lays ranks out row-major over
``("pod", "local")``.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

POD_AXIS = "pod"
LOCAL_AXIS = "local"
WORLD_AXES: Tuple[str, str] = (POD_AXIS, LOCAL_AXIS)


@dataclasses.dataclass(frozen=True)
class PodTopology:
    npods: int
    ppn: int  # chips per pod

    @property
    def nranks(self) -> int:
        return self.npods * self.ppn

    def pod_of(self, rank: int) -> int:
        return rank // self.ppn

    def local_of(self, rank: int) -> int:
        return rank % self.ppn

    def rank_of(self, pod: int, local: int) -> int:
        return pod * self.ppn + local

    # ------------------------------------------------------------------
    def agent_local(self, src_pod: int, dst_pod: int) -> int:
        """Pod-local rank of the 3-Step agent for the (src, dst) pod pair.

        The paper pairs "all processes with a receiving process on distinct
        nodes [to] ensure every process remains active"; ``(src+dst) % ppn``
        spreads agent duty over pod-local ranks so different pod pairs use
        different chips.
        """
        return (src_pod + dst_pod) % self.ppn

    def pod_shift_rounds(self) -> List[int]:
        """Inter-pod exchange rounds: pod shifts ``1 .. npods-1``."""
        return list(range(1, self.npods))


def make_exchange_mesh(topology: PodTopology) -> jax.sharding.Mesh:
    """Build a ``(npods, ppn)`` device mesh named ``("pod", "local")``.

    Requires ``jax.device_count() >= topology.nranks`` (tests use
    ``--xla_force_host_platform_device_count``).
    """
    if jax.device_count() < topology.nranks:
        raise ValueError(
            f"need {topology.nranks} devices for {topology}, "
            f"have {jax.device_count()}"
        )
    return jax.make_mesh((topology.npods, topology.ppn), WORLD_AXES)


def shard_ranks(a, mesh: jax.sharding.Mesh) -> jax.Array:
    """Place a ``[nranks, ...]`` array so each device of ``mesh`` holds only
    its own rank's slice (the layout every ``shard_map`` here expects)."""
    return jax.device_put(a, NamedSharding(mesh, P(WORLD_AXES)))
