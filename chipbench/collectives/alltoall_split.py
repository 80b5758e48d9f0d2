"""alltoall on a communicator split from the world, as NPB's MPI FT
issues its global transpose (``ft.f``): ``setup`` makes two
communicators of ``MPI_COMM_WORLD``,

    me1 = me / np2;  me2 = mod(me, np2)
    call MPI_Comm_split(MPI_COMM_WORLD, me1, me2, commslice1, ierr)
    call MPI_Comm_split(MPI_COMM_WORLD, me2, me1, commslice2, ierr)

and ``transpose2_global``, the 1-D layout's transpose, is
``mpi_alltoall(..., commslice1, ierr)``. In the 1-D layout (``np1 = 1``,
``np2 = np``: what ``setup`` chooses while ``np <= nz``, the class C
grid at 8 ranks) every rank has colour ``me1 = 0`` and key ``me2 = me``:
``commslice1`` holds all the ranks in world order and ``commslice2`` one
rank each. So rank ``r`` of ``commslice1`` is world rank ``r``, and what
each rank must hold afterwards is what ``alltoall.py`` says of an
alltoall on the world: the reference, the control and the arithmetic
are that module's, imported and not copied. What differs is the call,
and that the library has to carry it on the channel of a communicator
it derived (``expect.level_pvars`` names ``dev_coll_derived``).

The harness's loop ends by a word every rank reads between two
rendezvous with rank 0 (``harness.py``), so the communicator the call
runs on has to span the world; the disjoint rows of a 2-D layout
(``np1 > 1``) would not.
"""

from __future__ import annotations

from . import alltoall as _world

NAME = "alltoall"           # the spans' name: mpi:alltoall, dev_alltoall

NP1 = 1                     # the 1-D layout: np1 = 1, np2 = np

reference = _world.reference
lower_precision = _world.lower_precision
bus_factor = _world.bus_factor
least_bytes = _world.least_bytes


def setup(comm):
    """``ft.f``'s ``setup``, the two splits, once a rank; every rank of
    the world at once, as a collective over it must be. Kept on the
    rank's own ``comm`` object."""
    np2 = comm.size // NP1
    me1, me2 = comm.rank // np2, comm.rank % np2
    comm.commslice1 = comm.split(me1, me2)
    comm.commslice2 = comm.split(me2, me1)
    return comm.commslice1


def call(comm, x):
    """The served call, ``transpose2_global``'s: the alltoall on
    ``commslice1``. The first warm-up call makes the communicators."""
    slice1 = getattr(comm, "commslice1", None)
    if slice1 is None:
        slice1 = setup(comm)
    return slice1.alltoall(x)
