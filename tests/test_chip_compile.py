"""Ask the chip's compiler, without the chip.

The only test file that describes the TPU: the kernels of the device
path are lowered and compiled for a described (not attached) v5e at the
sizes the chip runs them, through their kernel wrappers with
``interpret=False`` — the dispatchers ask ``jax.default_backend()``,
see the CPU, and would compile nothing. A case passes when Mosaic
accepts the kernel and the compiled module holds a ``tpu_custom_call``;
what the compiler still refuses is a strict xfail that quotes it.

Topology, mesh and shardings are built inside module-scoped fixtures
(never at import): one process at a time may load libtpu, and every
xdist worker imports every test file.
"""

import functools
import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

KiB, MiB = 1 << 10, 1 << 20
P4 = 4


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile is written to the persistent cache
    # but can never be read back without a chip: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def ring_chunk_as_on_the_chip(monkeypatch):
    """The ring kernels' default chunk, said out loud: the CPU's measured
    profile (loaded for the life of the process once an earlier test of
    the same worker has bound ranks) cuts it to 2 KiB for the
    interpreter, and a 64 MiB ring then unrolls into tens of thousands
    of steps and traces for longer than the suite may run."""
    from mvapich2_tpu.utils.config import get_config
    monkeypatch.setenv("MV2T_ICI_CHUNK_BYTES", str(256 * KiB))
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """The four chips as the library binds them on the chip: the 1-D
    mesh ``make_mesh`` lays over the 2x2 (ISSUE 31: in ICI-neighbour
    order, ids 0, 1, 3, 2), so every case below compiles the program
    the chip runs, device assignment included."""
    from mvapich2_tpu.parallel.mesh import make_mesh
    return make_mesh((P4,), ("x",), topo.devices[:P4])


def _compile_sharded(mesh4, kernel, nelems, dtype):
    """Compile ``kernel(shard)`` under shard_map over the 4-chip mesh,
    one [nelems] shard per chip; returns the compiled module text."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    sm = jax.shard_map(lambda s: kernel(s.reshape(-1)).reshape(1, -1),
                       mesh=mesh4, in_specs=(P("x", None),),
                       out_specs=P("x", None), check_vma=False)
    x = jax.ShapeDtypeStruct((P4, nelems), dtype,
                             sharding=NamedSharding(mesh4, P("x", None)))
    return jax.jit(sm).lower(x).compile().as_text()


def _entry_ops(text):
    """``(op, dims)`` of every instruction of the compiled module's
    ENTRY computation: the HLO op's name and its result's dimensions as
    a list of strings (empty for a scalar or a tuple)."""
    import re
    line = re.compile(r"[^=]*=\s*(?:\w+\[([\d,]*)\]\S*|\(.*?\))"
                      r"\s([a-z][\w-]*)\(")
    return [(m.group(2), [d for d in (m.group(1) or "").split(",") if d])
            for m in map(line.match,
                         text[text.index("ENTRY"):].splitlines()[1:]) if m]


@pytest.mark.parametrize("kernel", ["hbm_ring_all_reduce", "hbm_alltoall"])
def test_ring_walks_the_2x2_in_neighbour_order(topo, mesh4, kernel):
    """The described 2x2 lists its chips row-major over (x, y): ids 0,
    1, 2, 3 at (0,0), (1,0), (0,1), (1,1). A ring in that order crosses
    the diagonal twice (1 -> 2, 3 -> 0), which no ICI link reaches;
    ``make_mesh`` walks 0, 1, 3, 2, every hop one link, and the two
    kernels of the four-chip cells compile over that device assignment
    (1 MiB a chip; the cells' sizes compile further down)."""
    from mvapich2_tpu.ops import pallas_alltoall, pallas_ici
    assert [(d.id, tuple(d.coords)[:2]) for d in topo.devices[:P4]] == [
        (0, (0, 0)), (1, (1, 0)), (2, (0, 1)), (3, (1, 1))]
    ring = list(mesh4.devices)
    assert [d.id for d in ring] == [0, 1, 3, 2]
    for a, b in zip(ring, ring[1:] + ring[:1]):
        assert sum(abs(p - q) for p, q in zip(a.coords, b.coords)) == 1
    fn = {"hbm_ring_all_reduce": lambda s: pallas_ici.hbm_ring_all_reduce(
              s, "x", P4, "sum", interpret=False),
          "hbm_alltoall": lambda s: pallas_alltoall.hbm_alltoall(
              s, "x", P4, interpret=False)}[kernel]
    text = _compile_sharded(mesh4, fn, MiB // 4, np.dtype("float32"))
    assert "tpu_custom_call" in text and "mv2t_" in text


def test_slot_allreduce_one_chip(one_chip):
    """The HBMSlotChannel kernel at chip_smoke's size: 8 ranks x 64 MiB
    f32 co-resident on one chip."""
    import jax
    import jax.numpy as jnp

    from mvapich2_tpu.ops import pallas_hbm
    x = jax.ShapeDtypeStruct((8, 64 * MiB // 4), jnp.float32,
                             sharding=one_chip)
    f = functools.partial(pallas_hbm.hbm_slot_allreduce, interpret=False)
    assert "tpu_custom_call" in jax.jit(f).lower(x).compile().as_text()


@pytest.mark.parametrize("nbytes", [4 * KiB, 64 * MiB])
def test_slot_program_reads_operands_in_place(topo, one_chip, monkeypatch,
                                              nbytes):
    """The program HBMSlotChannel runs on eight device-resident
    deposits, as its leader calls it: eight ``(n,)`` operands. Compiled
    for the chip it is the kernel between bitcasts: no copy, concatenate
    or fusion touches a rank's buffer on the way in (at 4 KiB the
    compiler may prefetch one operand, an async copy of 4 KiB)."""
    import jax
    import jax.numpy as jnp

    from mvapich2_tpu.coll.device import HBMSlotChannel, _Rendezvous
    from mvapich2_tpu.ops import _compat
    # the channel's programs ask the backend whether to interpret
    monkeypatch.setattr(_compat, "on_tpu", lambda: True)
    n = nbytes // 4
    ch = HBMSlotChannel(topo.devices[0], _Rendezvous(8), 0, 8)
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    text = ch._build("allreduce", n, "sum", 0).lower(
        *[x] * 8).compile().as_text()
    assert "tpu_custom_call" in text
    ops = {op for op, _ in _entry_ops(text)}
    assert {"parameter", "bitcast", "custom-call"} <= ops
    staging = ops - {"parameter", "bitcast", "custom-call"}
    assert staging <= ({"copy-start", "copy-done"} if nbytes == 4 * KiB
                       else set()), staging


def _slot_compiled(topo, one_chip, coll, nbytes):
    """``HBMSlotChannel``'s program for ``coll`` as its leader calls it
    on eight device-resident deposits, compiled for the chip: eight
    ``(n,)`` float32 operands of ``nbytes``. Returns the compiled
    program, its outputs' ``(shape, dtype)`` and its ENTRY's ops that
    are not plumbing (``parameter``, ``bitcast``, ``get-tuple-element``,
    ``tuple``); no operand is aliased to an output (the callers keep
    their send buffers)."""
    import jax
    import jax.numpy as jnp

    from mvapich2_tpu.coll.device import HBMSlotChannel, _Rendezvous
    ch = HBMSlotChannel(topo.devices[0], _Rendezvous(8), 0, 8)
    x = jax.ShapeDtypeStruct((nbytes // 4,), jnp.float32, sharding=one_chip)
    compiled = ch._build(coll, nbytes // 4, "sum", 0).lower(
        *[x] * 8).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 8 * nbytes
    assert mem.alias_size_in_bytes == 0
    assert "input_output_alias" not in compiled.as_text()
    outs = [(o.shape, o.dtype.name) for o in jax.tree.leaves(
        compiled.out_info)]
    moving = [op for op, _ in _entry_ops(compiled.as_text()) if op not in (
        "parameter", "bitcast", "get-tuple-element", "tuple")]
    return compiled, outs, moving


def test_slot_alltoall_at_the_fft_cell_size_fits_the_chip(topo, one_chip):
    """The program HBMSlotChannel runs for ``osu1.alltoall.128MiB.dev``:
    eight operands of 128 MiB. Since ISSUE 33 its outputs are the ranks'
    results: eight flat ``f32[33554432]``, output r block r of every
    operand in sender order. The chip's compiler takes it and asks for
    the 1 GiB of outputs beside the 1 GiB of operands and for no
    temporary: at most one fusion an operand, each reading its operand
    once and writing into all eight outputs in place, 2 GiB through HBM
    (the cell's ``least_bytes``); no stack, no transposed ``(8, 8, c)``,
    nothing left to cut out or relay afterwards. No ``mv2t_`` kernel
    runs in it, which is why the cell is on neither ``kernel_us`` nor
    ``kernel_roofline_pct``."""
    nbytes = 128 * MiB
    compiled, outs, moving = _slot_compiled(topo, one_chip, "alltoall",
                                            nbytes)
    mem = compiled.memory_analysis()
    assert 8 * nbytes <= mem.output_size_in_bytes < 8 * nbytes + KiB
    assert mem.temp_size_in_bytes < MiB
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and "mv2t_" not in text
    assert outs == [((nbytes // 4,), "float32")] * 8, outs
    assert 1 <= len(moving) <= 8 and set(moving) == {"fusion"}, moving


def test_slot_alltoall_at_16MiB_is_another_program(topo, one_chip):
    """``osu1.alltoall.16MiB.dev``'s program: the same eight fusions and
    eight flat outputs, but at 16 MiB a rank the compiler feeds them
    through memory space ``S(1)`` with asynchronous slices and copies
    around them, which the 128 MiB program has none of: 72 device ops a
    call on the chip where the 128 MiB cell runs 8 (PERF.md, PR 34)."""
    nbytes = 16 * MiB
    compiled, outs, moving = _slot_compiled(topo, one_chip, "alltoall",
                                            nbytes)
    mem = compiled.memory_analysis()
    assert 8 * nbytes <= mem.output_size_in_bytes < 8 * nbytes + KiB
    assert mem.temp_size_in_bytes < MiB
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and "mv2t_" not in text
    assert outs == [((nbytes // 4,), "float32")] * 8, outs
    assert moving.count("fusion") == 8
    assert set(moving) - {"fusion"} <= {"slice-start", "slice-done",
                                        "copy-start", "copy-done",
                                        "custom-call"}, moving


def test_slot_reduce_scatter_block_cuts_inside_the_program(topo, one_chip,
                                                           monkeypatch):
    """reduce_scatter_block on eight deposits of 64 MiB: the
    ``mv2t_slot_reduce`` call of the allreduce on the operands where
    they lie, then eight ``(c,)`` outputs cut inside the program;
    nothing for the leader to slice. No temporary beyond the
    ``n``-element reduction."""
    from mvapich2_tpu.ops import _compat
    # the channel's programs ask the backend whether to interpret
    monkeypatch.setattr(_compat, "on_tpu", lambda: True)
    nbytes = 64 * MiB
    compiled, outs, moving = _slot_compiled(
        topo, one_chip, "reduce_scatter_block", nbytes)
    mem = compiled.memory_analysis()
    assert nbytes <= mem.output_size_in_bytes < nbytes + KiB
    assert mem.temp_size_in_bytes <= nbytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mv2t_slot_reduce" in text
    assert outs == [((nbytes // 32,), "float32")] * 8, outs
    assert moving.count("custom-call") == 1 and len(moving) <= 9, moving


def _fold_channel(mesh4, monkeypatch):
    from mvapich2_tpu.coll.device import DeviceFoldChannel, _Rendezvous
    from mvapich2_tpu.ops import _compat
    # the fold program asks the backend whether to interpret
    monkeypatch.setattr(_compat, "on_tpu", lambda: True)
    ch = DeviceFoldChannel(mesh4, "x", _Rendezvous(8), 0, 8)
    assert (ch.k, ch.ndev, ch._mesh_extent()) == (2, 4, 4)
    return ch


@pytest.mark.parametrize("chip", [0, P4 - 1])
def test_fold_program_at_the_two_level_cell_size(topo, mesh4, monkeypatch,
                                                 chip):
    """Level 1 of ``osu4.allreduce_2level.64MiB.dev`` as the fold
    channel's leader calls it since ISSUE 41: one chip's two deposits of
    64 MiB, two flat operands where they lie, on the mesh's first and
    last device. Compiled for the chip the program is the
    ``mv2t_slot_reduce`` kernel between bitcasts: no stack, no relayout,
    no temporary, nothing aliased (the callers keep their buffers)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    ch = _fold_channel(mesh4, monkeypatch)
    nbytes = 64 * MiB
    x = jax.ShapeDtypeStruct(
        (nbytes // 4,), jnp.float32,
        sharding=SingleDeviceSharding(ch._mesh_devices[chip]))
    compiled = ch._fold_prog("sum").lower(x, x).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 2 * nbytes
    assert mem.output_size_in_bytes == nbytes
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes == 0
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mv2t_slot_reduce" in text
    moving = [op for op, _ in _entry_ops(text)
              if op not in ("parameter", "bitcast")]
    assert moving == ["custom-call"], moving


def test_fold_program_on_a_staged_host_deposit(topo, mesh4, one_chip,
                                               monkeypatch):
    """The form host deposits (and anything that does not lie flat on
    its chip) still take: the chip's two deposits stacked planar
    ``(2, n)`` by ``_chip_stack``, one operand of the same program,
    folded by ``mv2t_slot_reduce``. It compiles and fits; between the
    parameter and the kernel the compiler keeps one relayout fusion (the
    stack arrives tiled ``T(2,128)``, the kernel reads ``T(8,128)``)."""
    import jax
    import jax.numpy as jnp
    ch = _fold_channel(mesh4, monkeypatch)
    nbytes = 64 * MiB
    x = jax.ShapeDtypeStruct((2, nbytes // 4), jnp.float32,
                             sharding=one_chip)
    compiled = ch._fold_prog("sum").lower(x).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 2 * nbytes
    assert mem.output_size_in_bytes == nbytes
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes <= 2 * nbytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mv2t_slot_reduce" in text
    moving = [op for op, _ in _entry_ops(text)
              if op not in ("parameter", "bitcast")]
    assert moving.count("custom-call") == 1 and len(moving) <= 2, moving


@pytest.fixture
def default_tier_edges(monkeypatch):
    """The program's default tier edges, said out loud: once an earlier
    test of the same worker has bound ranks, the CPU's measured profile
    is loaded for the life of the process and sends every size to XLA."""
    from mvapich2_tpu.utils.config import get_config
    monkeypatch.setenv("MV2T_DEV_TIER_VMEM_MAX", str(4 * MiB))
    monkeypatch.setenv("MV2T_DEV_TIER_XLA_MIN", "-1")
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


@pytest.mark.parametrize("coll,nbytes,ring", [
    ("allreduce", 64 * MiB, "mv2t_hbm_all_reduce"),
    ("reduce_scatter_block", 4 * MiB, "mv2t_hbm_reduce_scatter")],
    ids=["allreduce_cell", "reduce_scatter_block"])
def test_fused_fold_program_is_one_kernel_and_the_root_copy(
        mesh4, monkeypatch, default_tier_edges, coll, nbytes, ring):
    """``osu4.allreduce_2level.64MiB.dev``'s one program (ISSUE 44), as
    the fold channel's leader builds it (``extra=k``): two flat
    mesh-sharded operands, per chip two parameters of the deposit's
    size as they lie. Compiled for the four chips it is, since ISSUE
    49, the ring kernel alone between bitcasts, both parameters its
    operands, and the one ROOT copy every four-chip program has: no
    ``mv2t_slot_reduce`` in front of the ring and no buffer of the
    deposit's size for a fold result (the kernel's outputs and the ROOT
    copy are all the program makes), no stack, relayout or fusion between a parameter and
    the kernel, nothing aliased (the callers keep their buffers). The
    same for reduce_scatter_block, at a small size."""
    import re

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mvapich2_tpu.ops import pallas_ici
    ch = _fold_channel(mesh4, monkeypatch)
    monkeypatch.setattr(pallas_ici, "on_tpu", lambda: True)
    n = nbytes // 4
    x = jax.ShapeDtypeStruct((P4 * n,), np.dtype("float32"),
                             sharding=NamedSharding(mesh4, P("x")))
    compiled = ch._build(coll, n, "sum", 0, ch.k).lower(x, x).compile()
    text = compiled.as_text()
    entry = _entry_ops(text)
    assert [dims for op, dims in entry if op == "parameter"] == \
        [[str(n)]] * 2, entry
    ops = [op for op, _ in entry if op not in (
        "parameter", "bitcast", "get-tuple-element", "tuple")]
    assert ops == ["custom-call", "copy"], ops
    # a Pallas call's instruction carries its kernel's name
    calls = re.findall(r"%(\w+?)(?:\.\d+)? = [^=]*? custom-call\(",
                       text[text.index("ENTRY"):])
    assert calls == [ring], calls
    assert "mv2t_slot_reduce" not in text
    # what the program allocates of a deposit's size: the kernel's
    # outputs (the allreduce's result; the reduce-scatter's working
    # buffer) and nothing for a fold result
    def elems(dims):
        return int(np.prod([int(d) for d in dims])) if dims else 0
    made = [op for op, dims in entry
            if op not in ("parameter", "bitcast") and elems(dims) >= n]
    assert made == (["custom-call", "copy"] if coll == "allreduce"
                    else []), made      # the latter's are one tuple
    assert not [dims for _, dims in entry if dims[:1] == ["1"]], entry
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 2 * nbytes
    assert mem.output_size_in_bytes == \
        (nbytes if coll == "allreduce" else nbytes // P4)
    assert mem.alias_size_in_bytes == 0
    assert "input_output_alias" not in text


@pytest.mark.parametrize("coll,dtype", [
    ("alltoall", "bfloat16"), ("alltoall", "float32"),
    ("allreduce", "float32"), ("allgather", "float32")])
def test_mesh_program_is_the_kernel_between_bitcasts(mesh4, monkeypatch,
                                                     default_tier_edges,
                                                     coll, dtype):
    """The program DeviceCollChannel runs on four device-resident
    deposits, as its leader calls it: one flat global ``(4 * n,)``
    operand sharded over ``x``, 4 MiB a rank. Compiled for the chips it
    is the kernel between bitcasts and at most one copy (the result out
    of the memory space the compiler keeps a cross-chip op's output in):
    nothing relays the payload out on the way in or out, and no shape
    with a leading 1 exists. Every case fails on the parent of PR 29,
    whose program takes a ``(4, n)`` operand; fed that, its bfloat16
    alltoall holds a ``reduce`` of the ``bf16[1, n]`` block before the
    kernel and a ``copy_bitcast_fusion`` to ``(p, c)`` after it (on the
    chip, at 192 MiB, 5.9 ms of relayouts around a 3.4 ms kernel)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mvapich2_tpu.coll.device import DeviceCollChannel, _Rendezvous
    from mvapich2_tpu.ops import _compat, pallas_ici
    # the tier dispatch asks the backend whether the kernels can run
    monkeypatch.setattr(_compat, "on_tpu", lambda: True)
    monkeypatch.setattr(pallas_ici, "on_tpu", lambda: True)
    dt = np.dtype(dtype)
    n = 4 * MiB // dt.itemsize
    ch = DeviceCollChannel(mesh4, "x", _Rendezvous(P4), 0)
    x = jax.ShapeDtypeStruct((P4 * n,), dt,
                             sharding=NamedSharding(mesh4, P("x")))
    text = ch._build(coll, n, "sum", 0).lower(x).compile().as_text()
    assert "tpu_custom_call" in text and "mv2t_" in text
    entry = _entry_ops(text)
    ops = [op for op, _ in entry]
    assert set(ops) <= {"parameter", "bitcast", "custom-call", "copy"}, ops
    assert ops.count("custom-call") == 1 and ops.count("copy") <= 1, ops
    assert not [dims for _, dims in entry if dims[:1] == ["1"]], entry


@pytest.mark.parametrize("n,moving,args,outs,temp", [
    # the cell: 16 MiB in, 64 MiB out, nothing held besides
    (8388608, ["custom-call", "copy"], 16 * MiB, 64 * MiB, 0),
    # Moonlight's own shard, 60 937.125 rows of 128: one pad in, the
    # padded blocks cut out of their own tiles and joined; a quarter of
    # the result as temporary (the parent: 24 ops, two ``while`` loops,
    # a temporary the size of the result; 3.6 ms on the chip for 1.0)
    (7799952, ["fusion", "custom-call", "fusion", "concatenate"],
     15601664, 62400512, 16 * MiB),
], ids=["cell", "moonlight"])
def test_allgather_program_at_its_own_size(mesh4, monkeypatch,
                                           default_tier_edges, n, moving,
                                           args, outs, temp):
    """``osu4.allgather.16MiB.dev``'s program as the leader builds it,
    at the cell's 8 388 608 bfloat16 a rank and at the FSDP shard the
    cell pads: the streaming ring between bitcasts and the one ROOT
    copy, or between one pad and one un-pad."""
    import jax
    import ml_dtypes
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mvapich2_tpu.coll.device import DeviceCollChannel, _Rendezvous
    from mvapich2_tpu.ops import _compat, pallas_ici
    monkeypatch.setattr(_compat, "on_tpu", lambda: True)
    monkeypatch.setattr(pallas_ici, "on_tpu", lambda: True)
    dt = np.dtype(ml_dtypes.bfloat16)
    assert pallas_ici.planned_tier("allgather", P4 * n * dt.itemsize, dt,
                                   None) == ("hbm", None)
    ch = DeviceCollChannel(mesh4, "x", _Rendezvous(P4), 0)
    x = jax.ShapeDtypeStruct((P4 * n,), dt,
                             sharding=NamedSharding(mesh4, P("x")))
    compiled = ch._build("allgather", n, None, 0).lower(x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mv2t_hbm_all_gather" in text
    ops = [op for op, _ in _entry_ops(text) if op not in (
        "parameter", "bitcast", "get-tuple-element", "tuple")]
    assert ops == moving, ops
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes, mem.output_size_in_bytes) == \
        (args, outs)
    assert mem.temp_size_in_bytes <= temp


@pytest.mark.parametrize("nbytes", [64 * MiB, 128 * MiB],
                         ids=["64MiB", "cell"])
def test_reduce_scatter_program_at_its_own_size(mesh4, monkeypatch,
                                                default_tier_edges, nbytes):
    """``osu4.reduce_scatter.128MiB.dev``'s program as the leader builds
    it, at the size ``test_hbm_ring_compiles`` takes the kernel to and at
    the cell's own 33 554 432 float32 a rank (twice that; lowered and
    compiled here in about 11 s): the ring's fold rounds alone between
    bitcasts and the one ROOT copy of the rank's quarter; a send buffer
    in, a quarter out, the kernel's working buffer (one send buffer and
    32 KiB; the partials on their way round live in it, the send buffer
    is read where it lies and never copied into it) the only
    temporary."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mvapich2_tpu.coll.device import DeviceCollChannel, _Rendezvous
    from mvapich2_tpu.ops import _compat, pallas_ici
    monkeypatch.setattr(_compat, "on_tpu", lambda: True)
    monkeypatch.setattr(pallas_ici, "on_tpu", lambda: True)
    dt = np.dtype("float32")
    n = nbytes // dt.itemsize
    assert pallas_ici.planned_tier("reduce_scatter_block", nbytes, dt,
                                   "sum") == ("hbm", None)
    # whole tiles: three quarters of the send buffer leave every chip
    assert pallas_ici.reduce_scatter_wire_bytes(n, dt, P4) == 3 * nbytes // 4
    ch = DeviceCollChannel(mesh4, "x", _Rendezvous(P4), 0)
    x = jax.ShapeDtypeStruct((P4 * n,), dt,
                             sharding=NamedSharding(mesh4, P("x")))
    compiled = ch._build("reduce_scatter_block", n, "sum",
                         0).lower(x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mv2t_hbm_reduce_scatter" in text
    entry = _entry_ops(text)
    ops = [op for op, _ in entry if op not in (
        "parameter", "bitcast", "get-tuple-element", "tuple")]
    assert ops == ["custom-call", "copy"], ops
    assert not [dims for _, dims in entry if dims[:1] == ["1"]], entry
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes, mem.output_size_in_bytes) == \
        (nbytes, nbytes // P4)
    assert nbytes <= mem.temp_size_in_bytes <= nbytes + 64 * KiB


@pytest.mark.parametrize("nbytes,root", [(64 * MiB, 0), (8 * MiB, 2)],
                         ids=["cell", "8MiB-root2"])
def test_bcast_program_at_its_own_size(mesh4, monkeypatch,
                                       default_tier_edges, nbytes, root):
    """``osu4.bcast.64MiB.dev``'s program as the leader builds it
    (ISSUE 51), at the cell's 33 554 432 bfloat16 a rank from rank 0
    (lowered and compiled here in under 2 s: the chain's steps are a
    loop's body, one schedule a role) and at an eighth of it from
    another root: the streaming chain between bitcasts and the one ROOT
    copy every four-chip program has. No ``all-reduce`` and no
    ``select`` anywhere in the module (the parent's one-hot ``psum``
    had both), nothing between the parameter and the kernel that could
    read a non-root's deposit, a buffer in, a buffer out, no temporary,
    nothing aliased (the root keeps its buffer)."""
    import re

    import jax
    import ml_dtypes
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mvapich2_tpu.coll.device import DeviceCollChannel, _Rendezvous
    from mvapich2_tpu.ops import _compat, pallas_ici
    monkeypatch.setattr(_compat, "on_tpu", lambda: True)
    monkeypatch.setattr(pallas_ici, "on_tpu", lambda: True)
    dt = np.dtype(ml_dtypes.bfloat16)
    n = nbytes // dt.itemsize
    assert pallas_ici.planned_tier("bcast", nbytes, dt, None,
                                   num_devices=P4) == ("hbm", None)
    # whole tiles: the root puts the message on the wire once
    assert pallas_ici.bcast_wire_bytes(n, dt, P4) == nbytes
    ch = DeviceCollChannel(mesh4, "x", _Rendezvous(P4), 0)
    x = jax.ShapeDtypeStruct((P4 * n,), dt,
                             sharding=NamedSharding(mesh4, P("x")))
    compiled = ch._build("bcast", n, "sum", root).lower(x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" not in text and "select" not in text
    entry = _entry_ops(text)
    ops = [op for op, _ in entry if op not in (
        "parameter", "bitcast", "get-tuple-element", "tuple")]
    assert ops == ["custom-call", "copy"], ops
    calls = re.findall(r"%(\w+?)(?:\.\d+)? = [^=]*? custom-call\(",
                       text[text.index("ENTRY"):])
    assert calls == ["mv2t_hbm_bcast"], calls
    assert not [dims for _, dims in entry if dims[:1] == ["1"]], entry
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes, mem.output_size_in_bytes,
            mem.temp_size_in_bytes) == (nbytes, nbytes, 0)
    assert mem.alias_size_in_bytes == 0
    assert "input_output_alias" not in text


_RING_SIZES = [(4 * KiB, "float32"), (1 * MiB, "float32"),
               (64 * MiB, "float32"), (1 * MiB, "bfloat16")]


@pytest.mark.parametrize("nbytes,dtype", _RING_SIZES)
@pytest.mark.parametrize("coll", ["all_reduce", "all_gather",
                                  "reduce_scatter"])
def test_hbm_ring_compiles(mesh4, coll, nbytes, dtype):
    """The three _RingStreamer kernels, both lanes, credits and entry
    barrier on, from one tile per block up to the 64 MiB shard."""
    from mvapich2_tpu.ops import pallas_ici
    fn = getattr(pallas_ici, f"hbm_ring_{coll}")
    dt = np.dtype(dtype)
    text = _compile_sharded(
        mesh4, lambda s: fn(s, "x", P4, interpret=False),
        nbytes // dt.itemsize, dt)
    assert "tpu_custom_call" in text


def test_hbm_ring_max_compiles(mesh4):
    """The non-sum reducer (chip_smoke --chips 4 runs max at 1 MiB)."""
    from mvapich2_tpu.ops import pallas_ici
    text = _compile_sharded(
        mesh4, lambda s: pallas_ici.hbm_ring_all_reduce(
            s, "x", P4, "max", interpret=False),
        MiB // 4, np.dtype("float32"))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("nbytes", [4 * KiB, 1 * MiB, 4 * MiB])
@pytest.mark.parametrize("coll", ["ring_all_reduce", "ring_all_gather"])
def test_vmem_ring_compiles(mesh4, coll, nbytes):
    """The VMEM-resident flat rings from one tile per block up to the
    tier's 4 MiB cap (shard for the allreduce, gathered output for the
    all-gather)."""
    from mvapich2_tpu.ops import pallas_ring
    fn = getattr(pallas_ring, coll)
    if coll == "ring_all_gather":
        nbytes //= P4
    text = _compile_sharded(
        mesh4, lambda s: fn(s, "x", P4, interpret=False),
        nbytes // 4, np.dtype("float32"))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("nbytes", [4 * MiB, 16 * MiB])
def test_alltoall_compiles(mesh4, nbytes):
    from mvapich2_tpu.ops import pallas_alltoall
    text = _compile_sharded(
        mesh4,
        lambda s: pallas_alltoall.hbm_alltoall(s, "x", P4,
                                               interpret=False),
        nbytes // 4, np.dtype("float32"))
    assert "tpu_custom_call" in text


def test_alltoall_bfloat16_compiles(mesh4):
    """The element type and tile of ``osu4.alltoall.192MiB.dev``:
    bfloat16, (16, 128) tiles, three permutation steps on both lanes.
    The cell's own shape, 48 MiB a pair, compiles too (ISSUE 28: 155 s
    on the sandbox's CPU, too long for this suite); pinned here is 16
    MiB a pair, the largest power of two that lowers in under a minute
    (37 s): the same kernel with a third of the chunks."""
    from mvapich2_tpu.ops import pallas_alltoall
    text = _compile_sharded(
        mesh4,
        lambda s: pallas_alltoall.hbm_alltoall(s, "x", P4,
                                               interpret=False),
        64 * MiB // 2, np.dtype("bfloat16"))
    assert "tpu_custom_call" in text


def test_alltoallv_compiles(mesh4):
    """Skewed counts, a zero pair, unaligned displacements."""
    from mvapich2_tpu.ops import pallas_alltoall
    counts = ((1000, 70000, 0, 5), (3, 128, 4096, 9), (0, 0, 1, 40000),
              (777, 12, 12, 12))
    _, _, in_len, _ = pallas_alltoall.packed_displs(counts)
    text = _compile_sharded(
        mesh4,
        lambda s: pallas_alltoall.hbm_alltoallv(s, "x", P4, counts,
                                                interpret=False),
        in_len, np.dtype("float32"))
    assert "tpu_custom_call" in text


def test_remote_sendrecv_compiles(mesh4):
    from mvapich2_tpu.ops import pallas_ici
    text = _compile_sharded(
        mesh4,
        lambda s: pallas_ici.remote_sendrecv(s, "x", P4, 0, 2,
                                             interpret=False),
        MiB // 4, np.dtype("float32"))
    assert "tpu_custom_call" in text


# -- the ROOT copy behind a communicating kernel (ISSUE 52, ROADMAP A3(e)) --

def _root_copy_probe(kind, x):
    """The least ``pallas_call`` of each kind: one whole-buffer copy of
    the operand into the output. ``remote``: to the right neighbour, one
    remote DMA; ``barrier``: a local DMA behind a signal and a wait on
    the barrier semaphore; ``local``: the same local DMA, nothing else;
    ``remote_aliased``: ``remote`` with the operand aliased to the
    output."""
    import jax
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from mvapich2_tpu.ops._compat import compiler_params

    def kernel(x_hbm, o_hbm, sem, recv_sem):
        right = (lax.rem(lax.axis_index("x") + 1, P4),)
        if kind == "barrier":
            barrier = pltpu.get_barrier_semaphore()
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=right,
                device_id_type=pltpu.DeviceIdType.MESH)
            pltpu.semaphore_wait(barrier, 1)
        if kind.startswith("remote"):
            cp = pltpu.make_async_remote_copy(
                src_ref=x_hbm, dst_ref=o_hbm, send_sem=sem,
                recv_sem=recv_sem, device_id=right,
                device_id_type=pltpu.DeviceIdType.MESH)
        else:
            cp = pltpu.make_async_copy(x_hbm, o_hbm, sem)
        cp.start()
        cp.wait()

    # a collective id goes with the barrier semaphore and only with it
    ids = dict(collective_id=13) if kind == "barrier" else {}
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())] * 2,
        compiler_params=compiler_params(has_side_effects=True, **ids),
        input_output_aliases={0: 0} if kind == "remote_aliased" else {},
        name=f"probe_{kind}")(x)


@pytest.mark.parametrize("kind,ops", [
    ("remote", ["parameter", "custom-call", "copy"]),
    ("barrier", ["parameter", "custom-call", "copy"]),
    ("local", ["parameter", "custom-call"]),
    ("remote_aliased", ["parameter", "copy-start", "copy-done",
                        "custom-call", "copy"]),
])
def test_a_communicating_kernel_is_followed_by_a_root_copy(mesh4, kind, ops):
    """What puts the ROOT ``copy`` behind every kernel of the four-chip
    cells (ROADMAP A3(e)), pinned so that nobody asks again: under the
    four-chip ``shard_map`` a ``pallas_call`` that holds one remote DMA,
    or only touches the barrier semaphore, compiles to ``custom-call``
    + ROOT ``copy`` of its whole result (8 192 x 128 float32 here); the
    same local copy with neither is ROOT itself. ``input_output_aliases``
    does not remove the copy: it adds a ``copy-start`` / ``copy-done``
    of the operand in front."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    rows = 8192
    sm = jax.shard_map(functools.partial(_root_copy_probe, kind),
                       mesh=mesh4, in_specs=(P("x", None),),
                       out_specs=P("x", None), check_vma=False)
    x = jax.ShapeDtypeStruct((P4 * rows, 128), np.dtype("float32"),
                             sharding=NamedSharding(mesh4, P("x", None)))
    text = jax.jit(sm).lower(x).compile().as_text()
    entry = _entry_ops(text)
    assert [op for op, _dims in entry] == ops
    assert all(dims == [str(rows), "128"] for op, dims in entry
               if op in ("custom-call", "copy", "copy-done"))
    root = [line for line in text[text.index("ENTRY"):].splitlines()
            if line.lstrip().startswith("ROOT")]
    assert len(root) == 1
    assert ("custom-call(" in root[0]) == (kind == "local")
    assert (f" copy(%probe_{kind}" in root[0]) == (kind != "local")


# -- what the chip's compiler still refuses (ISSUE 22 stop rule) --------
# These kernels are off chip_smoke's path. On a TPU they raise the
# compiler's error — no XLA fallback covers them — and each case below
# flips to a failure the day its kernel compiles (strict), so the marks
# cannot outlive the repair. ROADMAP A3 carries the work.

@pytest.mark.parametrize("n,disp", [(MiB // 4, 0), (100003, 77)])
@pytest.mark.parametrize("kind", ["put", "get", "accumulate"])
def test_rma_kernels_compile(mesh4, kind, n, disp):
    """The exact one-sided kernels over (rows, 128) segments — whole
    tiles, and an odd count at an unaligned displacement (the wrappers
    cut and restore the segment on the XLA side)."""
    from mvapich2_tpu.ops import pallas_rma
    win = 2 * MiB // 4

    def kernel(w):
        src = w[:n] * 2
        if kind == "put":
            return pallas_rma.rma_put(src, w, "x", P4, 0, 2, disp,
                                      interpret=False)
        if kind == "get":
            return pallas_rma.rma_get(w, n, "x", P4, 0, 2, disp,
                                      interpret=False)
        return pallas_rma.rma_accumulate(src, w, "x", P4, 0, 2, disp,
                                         interpret=False)
    assert "tpu_custom_call" in _compile_sharded(
        mesh4, kernel, win, np.dtype("float32"))


@pytest.mark.xfail(strict=True, reason=(
    "the quantized accumulate shares pallas_quant's codec (and keeps "
    "its flat 1-D slots): \"Mosaic failed to compile TPU kernel: "
    "infer-vector-layout: unsupported shape cast\""))
def test_rma_quantized_accumulate_compiles(mesh4):
    from mvapich2_tpu.ops import pallas_rma
    n = MiB // 4
    assert "tpu_custom_call" in _compile_sharded(
        mesh4,
        lambda w: pallas_rma.rma_accumulate(w * 2, w, "x", P4, 0, 2,
                                            quantized=True,
                                            interpret=False),
        n, np.dtype("float32"))


@pytest.mark.xfail(strict=True, reason=(
    "quant_ring_all_reduce packs 4 codes per int32 wire word with a "
    "reshape Mosaic cannot lay out: \"infer-vector-layout: unsupported "
    "shape cast\" on tpu.reshape (512x128xi32) -> (512x32x4xi32); its "
    "1-D (ndir, depth, chunk) slots also still put the slot index on a "
    "tiled dimension"))
def test_quant_ring_compiles(mesh4):
    from mvapich2_tpu.ops import pallas_quant
    text = _compile_sharded(
        mesh4,
        lambda s: pallas_quant.quant_ring_all_reduce(
            s, "x", P4, wire="q8", interpret=False),
        4 * MiB // 4, np.dtype("float32"))
    assert "tpu_custom_call" in text
