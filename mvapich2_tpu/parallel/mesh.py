"""Device-mesh communicators — binding MPI-style semantics to jax Meshes.

The analog of the reference's rank<->VC binding (SURVEY §3.1: MPIDI_PG /
VC tables) re-imagined for SPMD: a MeshComm names a mesh axis; "ranks" are
shards along that axis; collectives are the XLA-native ops from
mvapich2_tpu.ops. Hierarchical (2-level) communicators map to factored mesh
axes — intra-host axis over ICI-local devices + inter-host axis over DCN —
mirroring create_2level_comm's shmem/leader split (create_2level_comm.c:
57-96) with XLA's per-axis collective lowering doing the topology routing.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import mpit, ops
from ..utils.compile_cache import ensure_compile_cache
from ..utils.detect import detect
from ..utils.mlog import get_logger

log = get_logger("mesh")

shard_map = jax.shard_map


def mesh_shape_for(n: int, naxes: int = 2) -> Tuple[int, ...]:
    """Near-square factorization of n devices into naxes axes (the arch
    detect -> topology-shape step, mv2_arch_detect.c analog)."""
    if naxes == 1:
        return (n,)
    best = (1, n)
    for a in range(1, int(math.isqrt(n)) + 1):
        if n % a == 0:
            best = (a, n // a)
    if naxes == 2:
        return best
    rest = mesh_shape_for(best[1], naxes - 1)
    return (best[0],) + rest


def _ring_order(devices: Sequence[Any]) -> List[Any]:
    """The given devices in ICI-neighbour order for a 1-D axis: a snake
    over their ``coords`` in which consecutive devices are one hop apart
    in one coordinate. Out along the first line of chips, then back
    through the rest column by column in alternating direction, so the
    last device neighbours the first whenever the extent walked first
    is even (a 2x2 comes back as ids 0, 1, 3, 2; a 2x4 or a 4x4 closes
    too; a line of chips is sorted along itself). Read from what the
    devices say about themselves, no table per TPU generation. As given
    where that says nothing: no ``coords`` (CPU), one or two devices,
    several cores a chip (equal ``coords``), or chips that span more
    than two dimensions."""
    devices = list(devices)
    coords = [tuple(getattr(d, "coords", None) or ()) for d in devices]
    if (len(devices) <= 2 or not coords[0]
            or len(set(coords)) != len(devices)):
        return devices
    extent = {k: len({c[k] for c in coords}) for k in range(len(coords[0]))}
    dims = [k for k, e in extent.items() if e > 1]
    if len(dims) > 2:
        return devices
    at = dict(zip(coords, devices))
    if len(dims) == 1:
        return [at[c] for c in sorted(coords, key=lambda c: c[dims[0]])]
    # walk an even extent first where there is one: the snake then ends
    # beside its start (sorted is stable: x before y when both are even)
    a, b = sorted(dims, key=lambda k: extent[k] % 2)
    b0 = min(c[b] for c in coords)
    path = sorted((c for c in coords if c[b] == b0), key=lambda c: c[a])
    for k, av in enumerate(sorted({c[a] for c in coords}, reverse=True)):
        path += sorted((c for c in coords if c[a] == av and c[b] != b0),
                       key=lambda c: c[b], reverse=bool(k % 2))
    return [at[c] for c in path]


def make_mesh(shape: Optional[Sequence[int]] = None,
              axis_names: Sequence[str] = ("x",),
              devices=None) -> Mesh:
    """Build a Mesh over the first ``prod(shape)`` of the given devices
    (default: all of ``jax.devices()``). A multi-axis shape takes them
    row-major, as given. A one-axis shape promises the *set* of devices
    given, in ring order (as ``jax.make_mesh`` does): on TPU chips,
    consecutive positions, and the last and the first where the
    topology allows, are ICI neighbours (``_ring_order``), so rank r of
    a channel bound to the mesh lives on ``mesh.devices[r]``, which need
    not be ``devices[r]``. The pvar ``dev_mesh_reordered`` counts the
    meshes returned in another order than they were given."""
    ensure_compile_cache()
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if shape is None:
        shape = mesh_shape_for(n, len(axis_names))
    total = math.prod(shape)
    if total > n:
        raise ValueError(f"mesh shape {shape} needs {total} devices, "
                         f"have {n}")
    given = list(devices[:total])
    placed = _ring_order(given) if len(shape) == 1 else given
    if placed != given:
        mpit.pvar("dev_mesh_reordered").inc()
    return Mesh(np.asarray(placed).reshape(shape), tuple(axis_names))


class MeshComm:
    """A communicator over one mesh axis, several, or all axes.

    Inside a jitted/shard_mapped function, methods are the XLA collectives;
    outside, ``run`` wraps a function in shard_map over the mesh. The
    ``split``/``sub`` methods mirror MPI_Comm_split along orthogonal axes.

    ``axis`` may be a single axis name (the 1-D ring dispatch every PR
    before 20 had) or an ordered sequence of names — then the comm spans
    the product extent with ranks row-major over the named axes, and
    allreduce dispatches the multi-axis torus decomposition
    (ops/pallas_ici.ici_all_reduce_mesh: per-axis RS/AG ring phases
    above the dev_tier_axes_min edge). Movement collectives compose
    per-axis phases in the rank-order-preserving direction (gather
    innermost-first, scatter outermost-first, bcast from the root's
    per-axis coordinates innermost-first).
    """

    def __init__(self, mesh: Mesh, axis=None):
        self.mesh = mesh
        if axis is None:
            axis = mesh.axis_names[0]
        if isinstance(axis, (tuple, list)):
            self.axes: Tuple[str, ...] = tuple(str(a) for a in axis)
        else:
            self.axes = (str(axis),)
        for a in self.axes:
            if a not in mesh.axis_names:
                raise ValueError(f"axis {a!r} not in {mesh.axis_names}")
        self.axis = self.axes[0]

    # -- introspection ---------------------------------------------------
    @property
    def multi_axis(self) -> bool:
        return len(self.axes) > 1

    @property
    def size(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.axes)

    def axis_sizes(self) -> Tuple[Tuple[str, int], ...]:
        """Ordered (axis, extent) pairs this comm spans — the ``axes``
        argument of the ops-level multi-axis dispatchers."""
        return tuple((a, self.mesh.shape[a]) for a in self.axes)

    def rank(self):
        """Traced rank (call inside shard_map): the row-major flattened
        index over this comm's axes."""
        idx = ops.axis_rank(self.axes[0])
        for a in self.axes[1:]:
            idx = idx * self.mesh.shape[a] + ops.axis_rank(a)
        return idx

    def _coords(self, rank: int) -> Tuple[int, ...]:
        """Static per-axis coordinates of a flattened rank (row-major)."""
        out = []
        for a in reversed(self.axes):
            out.append(rank % self.mesh.shape[a])
            rank //= self.mesh.shape[a]
        return tuple(reversed(out))

    def sub(self, axis) -> "MeshComm":
        """Communicator over different axis/axes of the same mesh — the
        2-level split (e.g. 'host' × 'dcn' axes)."""
        return MeshComm(self.mesh, axis)

    # -- collectives (inside shard_map) ----------------------------------
    def allreduce(self, x, op: str = "sum"):
        if self.multi_axis:
            from ..ops import pallas_ici
            return pallas_ici.ici_all_reduce_mesh(
                x, self.axis_sizes(), op)
        return ops.allreduce(x, self.axis, op)

    def bcast(self, x, root: int = 0):
        if self.multi_axis:
            # innermost axis first: after bcasting axis k from the
            # root's coordinate on k, the root's whole k-line carries
            # the payload, so each outer phase fans a true copy
            coords = self._coords(root)
            for a, c in reversed(tuple(zip(self.axes, coords))):
                x = ops.bcast(x, a, c)
            return x
        return ops.bcast(x, self.axis, root)

    def all_gather(self, x, tiled: bool = False, gather_axis: int = 0):
        if self.multi_axis:
            for a in reversed(self.axes):   # innermost first: rank order
                x = ops.all_gather(x, a, tiled=tiled,
                                   gather_axis=gather_axis)
            return x
        return ops.all_gather(x, self.axis, tiled=tiled,
                              gather_axis=gather_axis)

    def reduce_scatter(self, x, scatter_dimension: int = 0):
        if self.multi_axis:
            for a in self.axes:             # outermost first: rank order
                x = ops.reduce_scatter(x, a,
                                       scatter_dimension=scatter_dimension)
            return x
        return ops.reduce_scatter(x, self.axis,
                                  scatter_dimension=scatter_dimension)

    def all_to_all(self, x, split_axis: int = 0, concat_axis: int = 0):
        return ops.all_to_all(x, self.axis, split_axis=split_axis,
                              concat_axis=concat_axis)

    def ring_shift(self, x, shift: int = 1):
        return ops.ring_shift(x, self.axis, shift)

    def halo_exchange(self, x, halo: int, dim: int = 0,
                      periodic: bool = True):
        return ops.halo_exchange(x, self.axis, halo, dim, periodic)

    def scan(self, x):
        return ops.scan_axis(x, self.axis)

    def barrier(self, token=None):
        return ops.barrier(self.axis)

    # -- launching SPMD regions ------------------------------------------
    def run(self, fn: Callable, *args, in_specs=None, out_specs=None,
            check_vma: bool = False):
        """shard_map ``fn`` over the mesh. Default: shard arg dim 0 over
        this axis; replicate output."""
        if in_specs is None:
            in_specs = tuple(P(self.axis) for _ in args)
        if out_specs is None:
            out_specs = P(self.axis)
        wrapped = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=check_vma)
        return wrapped(*args)

    def device_put_sharded(self, x, spec: Optional[P] = None):
        spec = spec if spec is not None else P(self.axis)
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def __repr__(self):
        return (f"MeshComm(axis={self.axis!r}, size={self.size}, "
                f"mesh={dict(self.mesh.shape)})")


@functools.lru_cache(maxsize=None)
def default_mesh_comm(naxes: int = 1) -> MeshComm:
    names = ("x", "y", "z")[:naxes]
    return MeshComm(make_mesh(axis_names=names))
