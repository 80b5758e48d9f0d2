"""The 1-D mesh walks TPU chips in ICI-neighbour order (ISSUE 31).

``parallel/mesh.make_mesh`` lays a one-axis mesh along a snake over the
devices' ``coords`` (``_ring_order``), so consecutive ring positions, and
the last and the first where an extent is even, are one hop apart. The
ordering is tested on fake devices that carry ``id`` and ``coords`` (CPU
devices have none, and come back as given); that the channels follow a
reordered mesh is ``tests/test_device_channel.py``'s, on four CPU devices
with the helper patched.
"""

import numpy as np
import pytest


class Chip:
    """What ``_ring_order`` reads of a device: ``coords`` (and an ``id``
    for the tests to name it by). TPU coords are (x, y, z)."""

    def __init__(self, id_, coords=None):
        self.id = id_
        if coords is not None:
            self.coords = coords

    def __repr__(self):
        return f"Chip({self.id}, {getattr(self, 'coords', None)})"


def _grid(nx, ny):
    """An nx x ny slice in the order a TPU runtime lists it: x fastest."""
    return [Chip(y * nx + x, (x, y, 0)) for y in range(ny) for x in range(nx)]


def _hops(a, b):
    return sum(abs(p - q) for p, q in zip(a.coords, b.coords))


# name -> (devices as given, mesh shape, ids expected or None for "only
# the properties", closed: last neighbours first)
CASES = {
    "2x2": (_grid(2, 2), (4,), [0, 1, 3, 2], True),
    "2x2_given_in_ring_order": (
        [_grid(2, 2)[i] for i in (0, 1, 3, 2)], (4,), [0, 1, 3, 2], True),
    "2x4": (_grid(2, 4), (8,), None, True),
    "4x2": (_grid(4, 2), (8,), None, True),
    "4x4": (_grid(4, 4), (16,), None, True),
    "3x4_odd_extent_first": (_grid(3, 4), (12,), None, True),
    "3x3_cannot_close": (_grid(3, 3), (9,), None, False),
    "three_of_a_2x2": (_grid(2, 2)[:3], (3,), [0, 1, 2], None),
    "1x4_line": (_grid(1, 4), (4,), [0, 1, 2, 3], False),
    "4x1_line_given_backwards": (
        _grid(4, 1)[::-1], (4,), [0, 1, 2, 3], False),
    "no_coords": ([Chip(i) for i in range(4)], (4,), [0, 1, 2, 3], None),
    "two_devices": (_grid(2, 1)[::-1], (2,), [1, 0], None),
    "two_cores_a_chip": (
        [Chip(i, (i // 2 % 2, i // 4, 0)) for i in range(8)], (8,),
        list(range(8)), None),
    "three_dimensions": (
        [Chip(i, (i % 2, i // 2 % 2, i // 4)) for i in range(8)], (8,),
        list(range(8)), None),
    "multi_axis_shape": (_grid(2, 2), (2, 2), [0, 1, 2, 3], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_ring_order(case):
    """``make_mesh`` returns a permutation of the devices it was given;
    on chips of a line or a plane every consecutive pair is one hop
    apart in one coordinate and, where ``closed``, so are the last and
    the first; everything else comes back as given. The pvar
    ``dev_mesh_reordered`` rises exactly when the order changed."""
    from mvapich2_tpu import mpit
    from mvapich2_tpu.parallel.mesh import make_mesh
    given, shape, ids, closed = CASES[case]
    before = mpit.pvar("dev_mesh_reordered").read()
    mesh = make_mesh(shape, ("x", "y")[:len(shape)], given)
    got = list(np.asarray(mesh.devices).reshape(-1))
    assert mesh.devices.shape == shape
    assert sorted(d.id for d in got) == sorted(d.id for d in given)
    if ids is not None:
        assert [d.id for d in got] == ids
    if closed is not None:
        assert all(_hops(a, b) == 1 for a, b in zip(got, got[1:])), got
        assert (_hops(got[-1], got[0]) == 1) == closed, got
    moved = [d.id for d in got] != [d.id for d in given]
    assert mpit.pvar("dev_mesh_reordered").read() - before == int(moved)


def test_takes_the_first_devices_of_a_longer_list():
    """``make_mesh((4,), ..., devices)`` still means the first four of
    the list, whatever order it then walks them in."""
    from mvapich2_tpu.parallel.mesh import make_mesh
    mesh = make_mesh((4,), ("x",), _grid(2, 4))
    assert [d.id for d in mesh.devices] == [0, 1, 3, 2]
