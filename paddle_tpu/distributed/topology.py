"""Process topology → jax device mesh.

Parity surface: python/paddle/distributed/fleet/base/topology.py
(``CommunicateTopology``, ``HybridCommunicateGroup`` — the 4-5D process
"mesh" of dp × pp × sharding × mp × sep built from comm groups). TPU-native
design: the topology IS a ``jax.sharding.Mesh`` with named axes; per-axis
"communication groups" are just axis names handed to collectives, and XLA
routes them over ICI. One ``HybridCommunicateGroup`` activates globally
(mirroring fleet's singleton hcg).
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["CommunicateTopology", "HybridCommunicateGroup", "ProcessGroup",
           "get_hybrid_communicate_group", "set_hybrid_communicate_group",
           "global_mesh", "new_group"]

# canonical axis order mirrors fleet's default hybrid order
_AXIS_ORDER = ("pp", "dp", "sharding", "sep", "mp")


class ProcessGroup:
    """A communication group = a mesh axis (or the trivial 1-axis world).

    Parity: the reference's ProcessGroup handle (upstream
    paddle/fluid/distributed/collective/process_group.h). ``axis_name``
    addresses collectives; ``ranks`` lists member positions along that axis.
    """

    _next_gid = itertools.count(1)  # 0 is the world group

    # gid -> group, weakly held (groups are created per call by the hcg
    # accessors — strong registry references would grow without bound and
    # outlive their meshes). gid 0 is RESERVED for the world group.
    import weakref as _weakref
    _registry: "ProcessGroup._weakref.WeakValueDictionary" = \
        _weakref.WeakValueDictionary()

    def __init__(self, mesh: Mesh, axis_name: Optional[str], ranks=None,
                 rank: int = 0):
        self.id = next(ProcessGroup._next_gid)
        ProcessGroup._registry[self.id] = self
        self.mesh = mesh
        self.axis_name = axis_name
        self.nranks = int(mesh.shape[axis_name]) if axis_name else 1
        self.ranks = list(ranks) if ranks is not None else list(range(self.nranks))
        self.rank = rank

    @property
    def world_size(self) -> int:
        return self.nranks

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"ProcessGroup(axis={self.axis_name}, nranks={self.nranks})"


class CommunicateTopology:
    def __init__(self, hybrid_group_names: Sequence[str] = ("data", "pipe",
                                                            "sharding", "sep", "model"),
                 dims: Sequence[int] = (1, 1, 1, 1, 1)):
        self._names = list(hybrid_group_names)
        self._dims = list(int(d) for d in dims)
        self._world = int(np.prod(self._dims))

    def get_hybrid_group_names(self) -> List[str]:
        return self._names

    def get_dim(self, name: str) -> int:
        return self._dims[self._names.index(name)]

    get_dim_size = get_dim

    def world_size(self) -> int:
        return self._world

    def get_rank(self, **kwargs) -> int:
        coord = [kwargs[n] for n in self._names]
        return int(np.ravel_multi_index(coord, self._dims))

    def get_coord(self, rank: int):
        return tuple(int(c) for c in np.unravel_index(rank, self._dims))

    def get_axis_list(self, axis_name: str, index: int) -> List[int]:
        axis = self._names.index(axis_name)
        ranks = []
        for r in range(self._world):
            if self.get_coord(r)[axis] == index:
                ranks.append(r)
        return ranks

    def get_comm_list(self, axis_name: str) -> List[List[int]]:
        axis = self._names.index(axis_name)
        groups: Dict[tuple, List[int]] = {}
        for r in range(self._world):
            c = list(self.get_coord(r))
            c[axis] = -1
            groups.setdefault(tuple(c), []).append(r)
        return list(groups.values())


class HybridCommunicateGroup:
    """Builds the hybrid mesh. Axis names on the jax Mesh: dp, pp, sharding,
    sep, mp (only axes with degree > 1 when ``squeeze`` is True)."""

    def __init__(self, dp_degree: int = 1, mp_degree: int = 1,
                 pp_degree: int = 1, sharding_degree: int = 1,
                 sep_degree: int = 1, order: Optional[Sequence[str]] = None,
                 devices=None):
        self._degrees = {"dp": dp_degree, "mp": mp_degree, "pp": pp_degree,
                         "sharding": sharding_degree, "sep": sep_degree}
        order = tuple(order) if order else _AXIS_ORDER
        self._order = order
        devices = list(devices) if devices is not None else jax.devices()
        total = int(np.prod(list(self._degrees.values())))
        if total > len(devices):
            raise ValueError(
                f"hybrid degrees {self._degrees} need {total} devices, "
                f"only {len(devices)} available")
        devices = devices[:total]
        shape = [self._degrees[a] for a in order]
        self.mesh = Mesh(np.array(devices).reshape(shape), order)
        set_hybrid_communicate_group(self)
        self._topology = CommunicateTopology(
            hybrid_group_names=list(order), dims=shape)

    # --- parity getters ------------------------------------------------------
    @property
    def topology(self) -> CommunicateTopology:
        return self._topology

    def _group(self, axis: str) -> ProcessGroup:
        return ProcessGroup(self.mesh, axis if self._degrees[axis] > 1 else axis)

    def get_parallel_mode(self) -> str:
        if self._degrees["pp"] > 1:
            return "pipeline"
        if self._degrees["sharding"] > 1:
            return "sharding_parallel"
        if self._degrees["mp"] > 1:
            return "model"
        return "data"

    # world sizes
    def get_data_parallel_world_size(self) -> int:
        return self._degrees["dp"]

    def get_model_parallel_world_size(self) -> int:
        return self._degrees["mp"]

    def get_pipe_parallel_world_size(self) -> int:
        return self._degrees["pp"]

    def get_sharding_parallel_world_size(self) -> int:
        return self._degrees["sharding"]

    def get_sep_parallel_world_size(self) -> int:
        return self._degrees["sep"]

    # groups (mesh-axis handles)
    def get_data_parallel_group(self) -> ProcessGroup:
        return self._group("dp")

    def get_model_parallel_group(self) -> ProcessGroup:
        return self._group("mp")

    def get_pipe_parallel_group(self) -> ProcessGroup:
        return self._group("pp")

    def get_sharding_parallel_group(self) -> ProcessGroup:
        return self._group("sharding")

    def get_sep_parallel_group(self) -> ProcessGroup:
        return self._group("sep")

    def get_check_parallel_group(self, *a) -> ProcessGroup:
        return self._group("mp")

    # ranks: single-process SPMD has no per-process coordinate; expose 0 for
    # parity (mesh positions replace ranks inside compiled programs)
    def get_data_parallel_rank(self) -> int:
        return 0

    def get_model_parallel_rank(self) -> int:
        return 0

    def get_stage_id(self) -> int:
        return 0

    def get_sharding_parallel_rank(self) -> int:
        return 0

    def get_global_rank(self) -> int:
        from .env import get_rank
        return get_rank()


_hcg: Optional[HybridCommunicateGroup] = None
_default_mesh: Optional[Mesh] = None


_topology_epoch = 0


def topology_epoch() -> int:
    """Monotonic counter bumped on every hybrid-topology (re)set — cache
    keys derived from the live topology use this instead of object ids
    (CPython id reuse would alias a dead mesh's cache entries)."""
    return _topology_epoch


def set_hybrid_communicate_group(hcg: HybridCommunicateGroup) -> None:
    global _hcg, _topology_epoch
    _hcg = hcg
    _topology_epoch += 1
    # purge topology-scoped caches EAGERLY: dist.split's cached layers hold
    # registered state tensors committed to the OLD mesh — left alive, they
    # ride into every later to_static state signature and collide with the
    # new mesh's device set (the lazy next-call purge is not enough when
    # split is never called again)
    try:
        from .comm import _SPLIT_LAYERS
        _SPLIT_LAYERS.clear()
    except ImportError:  # pragma: no cover - circular-import guard
        pass


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _hcg


def multi_device_mesh() -> Optional[Mesh]:
    """The fleet hybrid mesh when it spans more than one device, else
    ``None`` — what the Pallas call sites ask: a Mosaic kernel cannot be
    partitioned automatically, so under such a mesh it runs per shard
    (flash attention) or yields to an XLA formulation (the q8 update)."""
    return _hcg.mesh if _hcg is not None and _hcg.mesh.size > 1 else None


def _ensure_default_topology() -> None:
    """Default 1D dp mesh over all local devices (init_parallel_env path)."""
    global _default_mesh
    if _hcg is None and _default_mesh is None:
        devs = jax.devices()
        _default_mesh = Mesh(np.array(devs), ("dp",))


def global_mesh() -> Mesh:
    """The active mesh: the hybrid mesh if fleet initialized one, else the
    default dp mesh over all devices."""
    if _hcg is not None:
        return _hcg.mesh
    _ensure_default_topology()
    return _default_mesh


def new_group(ranks=None, backend=None, timeout=None) -> ProcessGroup:
    """Parity: paddle.distributed.new_group. Groups are mesh-axis handles;
    a rank-list subset of the world maps onto the dp axis of the active
    mesh (arbitrary subsets would need their own sub-mesh — supported for the
    common all-ranks case)."""
    mesh = global_mesh()
    axis = mesh.axis_names[0]
    return ProcessGroup(mesh, axis, ranks=ranks)
