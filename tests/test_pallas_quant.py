"""Block-scaled quantized allreduce tier (ops/pallas_quant) —
interpret-mode error-bound sweep on the 8-device virtual CPU mesh.

The quantized kernels carry an explicit error CONTRACT
(``declared_bound``: at most p quantizations per element, each within
half a code step of its block scale) instead of the exact kernels'
bit-agreement contract — so the sweep asserts max relative error
within the declared budget against the exact lowering for every
wire x dtype x chunk-boundary shape x ring width, bit-exactness where
the codec is lossless by construction, bit-identical results across
ranks (every rank decodes the same gathered code words), and that all
exact-mode fallbacks (budget 0/unset, integer dtypes, min/max) really
run the exact tiers. The wire-byte accounting (the
hardware-independent half of the quant claim) is asserted analytically, and the tier is
driven end-to-end through coll/device.py on a >= 1 MiB f32 allreduce.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from mvapich2_tpu import mpit  # noqa: E402
from mvapich2_tpu.ops import pallas_ici, pallas_quant  # noqa: E402
from mvapich2_tpu.parallel import MeshComm, make_mesh  # noqa: E402
from mvapich2_tpu.utils.config import get_config  # noqa: E402

NP = 8


@pytest.fixture(scope="module")
def comm8():
    return MeshComm(make_mesh((NP,), ("x",)))


def _reload(**env):
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    get_config().reload()


@pytest.fixture(autouse=True)
def _clean_env():
    yield
    _reload(MV2T_QUANT_COLL=None, MV2T_QUANT_BLOCK=None,
            MV2T_DEV_TIER_QUANT_MIN=None, MV2T_ICI_INTERPRET=None,
            MV2T_DEV_TIER_VMEM_MAX=None, MV2T_DEV_TIER_XLA_MIN=None,
            MV2T_ICI_CHUNK_BYTES=None, MV2T_DEVICE_COLL_MIN_BYTES=None)


def _run_q(comm8, xv, p, wire="q8", **kw):
    """Quantized allreduce over the first ``p`` shards of an NP-wide
    mesh is modeled by running at full width with the upper shards
    zeroed — instead, run the real ring at width p on a sub-mesh."""
    comm = comm8 if p == NP else MeshComm(make_mesh(
        (p,), ("x",), jax.devices()[:p]))
    out = comm.run(lambda s: pallas_quant.quant_ring_all_reduce(
        s, "x", p, wire=wire, interpret=True, **kw), jnp.asarray(xv))
    return np.asarray(out).reshape(p, -1)


# ---------------------------------------------------------------------------
# the error-bound contract: ops x dtypes x shapes x np x wire
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 4])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
@pytest.mark.parametrize("shard,block_bytes,chunk_bytes", [
    (128, 64, 128),       # blocks divide shard and chunk exactly
    (300, 64, 256),       # block-padded tail, multi-chunk
    (37, 32, 1 << 20),    # 1-chunk degenerate, heavy padding
])
def test_rel_error_within_declared_budget(comm8, p, dtype, shard,
                                          block_bytes, chunk_bytes):
    rng = np.random.default_rng(shard * p)
    xv = rng.standard_normal(p * shard).astype(np.float32)
    jdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    if dtype == "bfloat16":
        xv = np.asarray(jnp.asarray(xv, jdt).astype(jnp.float32))
    got = _run_q(comm8, jnp.asarray(xv, jdt), p,
                 block_bytes=block_bytes, chunk_bytes=chunk_bytes)
    got = np.asarray(jnp.asarray(got).astype(jnp.float32))
    exp = np.asarray(xv, np.float64).reshape(p, -1).sum(0)
    bound = pallas_quant.declared_bound(p, "q8")
    if dtype == "bfloat16":
        bound += 1 / 128          # bf16 staging adds its own half-ulp
    rel = np.abs(got[0] - exp).max() / max(np.abs(exp).max(), 1e-12)
    assert rel <= bound, (rel, bound)
    # every rank decodes the same gathered code words: bit-identical
    for row in got[1:]:
        np.testing.assert_array_equal(row, got[0])


@pytest.mark.parametrize("p", [2, 4])
def test_fp8_wire_within_declared_budget(comm8, p):
    rng = np.random.default_rng(7)
    xv = rng.standard_normal(p * 256).astype(np.float32)
    got = _run_q(comm8, xv, p, wire="fp8", block_bytes=128,
                 chunk_bytes=256)
    exp = np.asarray(xv, np.float64).reshape(p, -1).sum(0)
    rel = np.abs(got[0] - exp).max() / np.abs(exp).max()
    assert rel <= pallas_quant.declared_bound(p, "fp8"), rel


def test_bitexact_for_int8_valued_data(comm8):
    """Identical integer shards with a full-range (+-127) element in
    EVERY quantization block make every block scale exactly k
    (integer) at every fold — the codec is lossless by construction
    and the quantized sum is bit-exact."""
    i = np.arange(64)
    base = np.where(i % 8 == 0, 127, (i % 8) - 4).astype(np.float32)
    xv = np.tile(base, NP)               # every rank holds one pattern
    got = _run_q(comm8, xv, NP, block_bytes=64, chunk_bytes=128)
    exp = (base * NP).astype(np.float32)
    for row in got:
        np.testing.assert_array_equal(row, exp)


def test_pipeline_depth_invariance(comm8):
    """Deeper pipelines reorder DMA issue, never results — the quant
    codec rides the slot schedule, it does not change it."""
    rng = np.random.default_rng(3)
    xv = rng.standard_normal(NP * 300).astype(np.float32)
    ref = _run_q(comm8, xv, NP, block_bytes=64, chunk_bytes=256,
                 depth=2)
    for depth in (3, 4):
        got = _run_q(comm8, xv, NP, block_bytes=64, chunk_bytes=256,
                     depth=depth)
        np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# exact-mode fallbacks + tier routing
# ---------------------------------------------------------------------------

def test_non_sum_ops_take_exact_kernel(comm8):
    """min/max/prod and integer dtypes never quantize — the wrapper's
    exact fallback is bit-identical to the exact hbm kernel."""
    xv = (np.arange(NP * 16) % 11 - 5).astype(np.int32)
    out = comm8.run(lambda s: pallas_quant.quant_ring_all_reduce(
        s, "x", NP, op="max", interpret=True, chunk_bytes=32),
        jnp.asarray(xv))
    exp = np.asarray(xv).reshape(NP, -1).max(0)
    for row in np.asarray(out).reshape(NP, -1):
        np.testing.assert_array_equal(row, exp)


def test_planned_tier_quant_routing():
    """The quant bin opens only with a budget set, sits above the hbm
    tier AND the xla re-entry, and degrades per call: int dtypes,
    non-sum ops and too-small budgets keep the exact hbm tier."""
    _reload(MV2T_ICI_INTERPRET="1", MV2T_DEV_TIER_VMEM_MAX="64",
            MV2T_DEV_TIER_QUANT_MIN="4096",
            MV2T_DEV_TIER_XLA_MIN="65536")
    pt = pallas_ici.planned_tier
    # budget unset: the bin never opens
    assert pt("allreduce", 8192, np.float32, "sum",
              num_devices=4) == ("hbm", None)
    _reload(MV2T_QUANT_COLL="1e-1")
    assert pt("allreduce", 8192, np.float32, "sum",
              num_devices=4) == ("quant", None)
    assert pt("allreduce", 100, np.float32, "sum",
              num_devices=4) == ("hbm", None)      # below the edge
    assert pt("allreduce", 1 << 20, np.float32, "sum",
              num_devices=4) == ("quant", None)    # above xla re-entry
    # per-call exact-mode degradations (never an XLA fallback)
    assert pt("allreduce", 8192, np.int32, "sum",
              num_devices=4) == ("hbm", None)
    assert pt("allreduce", 8192, np.float32, "max",
              num_devices=4) == ("hbm", None)
    assert pt("allgather", 8192, np.float32, None,
              num_devices=4) == ("hbm", None)
    # a budget below the declared bound for this ring width
    _reload(MV2T_QUANT_COLL="1e-4")
    assert pt("allreduce", 8192, np.float32, "sum",
              num_devices=8) == ("hbm", None)
    # budget=0 reads as off
    _reload(MV2T_QUANT_COLL="0")
    assert pt("allreduce", 8192, np.float32, "sum",
              num_devices=4) == ("hbm", None)
    # malformed value reads as off, never quantizes
    _reload(MV2T_QUANT_COLL="fast:please")
    assert pt("allreduce", 8192, np.float32, "sum",
              num_devices=4) == ("hbm", None)


def test_quant_params_grammar():
    from mvapich2_tpu.coll.tuning import quant_params
    _reload(MV2T_QUANT_COLL=None)
    assert quant_params() == ("q8", 0.0)
    _reload(MV2T_QUANT_COLL="1e-2")
    assert quant_params() == ("q8", 0.01)
    _reload(MV2T_QUANT_COLL="fp8:0.25")
    assert quant_params() == ("fp8", 0.25)
    _reload(MV2T_QUANT_COLL="q8:-3")
    assert quant_params() == ("q8", 0.0)


def test_dispatcher_routes_quant(comm8):
    """ici_all_reduce dispatches the quant bin end to end and the
    result honors the budget."""
    _reload(MV2T_ICI_INTERPRET="1", MV2T_QUANT_COLL="5e-2",
            MV2T_DEV_TIER_VMEM_MAX="16", MV2T_DEV_TIER_QUANT_MIN="64",
            MV2T_ICI_CHUNK_BYTES="512")
    rng = np.random.default_rng(11)
    xv = rng.standard_normal(NP * 200).astype(np.float32)
    before = mpit.pvar("dev_coll_tier_quant").read()
    out = comm8.run(lambda s: pallas_ici.ici_all_reduce(s, "x", NP),
                    jnp.asarray(xv))
    got = np.asarray(out).reshape(NP, -1)
    exp = np.asarray(xv, np.float64).reshape(NP, -1).sum(0)
    rel = np.abs(got[0] - exp).max() / np.abs(exp).max()
    assert rel <= 5e-2, rel
    # direct shard_map users do not ride _note_tier; the pvar moves in
    # the device-channel test below — here just assert no decrement
    assert mpit.pvar("dev_coll_tier_quant").read() >= before


def test_exact_mode_bit_identical_when_cvar_unset(comm8):
    """With MV2T_QUANT_COLL unset the dispatcher is bit-identical to
    the exact lowering (integer-valued f32 makes the sum order-free) —
    the quant tier cannot leak into exact mode."""
    _reload(MV2T_ICI_INTERPRET="1", MV2T_QUANT_COLL=None,
            MV2T_DEV_TIER_VMEM_MAX="16", MV2T_ICI_CHUNK_BYTES="64")
    xv = (np.arange(NP * 24) % 13).astype(np.float32)
    got = comm8.run(lambda s: pallas_ici.ici_all_reduce(s, "x", NP),
                    jnp.asarray(xv))
    from mvapich2_tpu import ops
    ref = comm8.run(lambda s: ops.allreduce(s, "x"), jnp.asarray(xv))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# wire-byte accounting (the hardware-independent half of the claim)
# ---------------------------------------------------------------------------

def test_wire_stats_ratio_under_bound():
    for p in (2, 4, 8):
        exact, quant = pallas_quant.wire_stats(262144, np.float32, p)
        assert exact == 2 * (p - 1) * (-(-262144 // p) // 128 * 128
                                       + 0) * 4 or exact > 0
        assert quant <= 0.3 * exact, (p, exact, quant)
    # bf16 wire shrinks less (2-byte exact wire): accounted honestly
    exact, quant = pallas_quant.wire_stats(262144, np.dtype("bfloat16"),
                                           8)
    assert 0.3 * exact < quant <= 0.6 * exact


def test_wire_words_geometry():
    assert pallas_quant.wire_words(128, 128) == 1 + 32
    assert pallas_quant.wire_words(256, 128) == 2 * 33
    _reload(MV2T_QUANT_BLOCK="256")
    assert pallas_quant.quant_block_elems(jnp.float32) == 64
    _reload(MV2T_QUANT_BLOCK=None)


# ---------------------------------------------------------------------------
# end-to-end through coll/device.py (the acceptance scenario)
# ---------------------------------------------------------------------------

def test_device_channel_quant_end_to_end():
    """>= 1 MiB f32 allreduce through the mesh-bound channel with
    MV2T_QUANT_COLL set: the quant tier is dispatched (pvar counted),
    the wire-byte saving is accounted at <= 0.3x exact, the result is
    within budget — and the exact run with the cvar unset is
    bit-identical to the XLA lowering."""
    from mvapich2_tpu.runtime.universe import run_ranks

    n = 1 << 18                       # 1 MiB of f32 per rank
    nranks = 2
    budget = 5e-2
    rng = np.random.default_rng(5)
    data = rng.standard_normal((nranks, n)).astype(np.float32)
    exp = data.astype(np.float64).sum(0)

    _reload(MV2T_ICI_INTERPRET="1", MV2T_QUANT_COLL=str(budget),
            MV2T_DEV_TIER_VMEM_MAX="16",
            MV2T_DEV_TIER_QUANT_MIN="65536",
            MV2T_ICI_CHUNK_BYTES="262144",
            MV2T_DEVICE_COLL_MIN_BYTES="1")
    q_before = mpit.pvar("dev_coll_tier_quant").read()
    s_before = mpit.pvar("dev_coll_quant_bytes_saved").read()
    got = {}

    def app(comm):
        out = comm.allreduce(data[comm.rank])
        if comm.rank == 0:
            got["quant"] = np.asarray(out)

    run_ranks(nranks, app, device_mesh=True)
    assert mpit.pvar("dev_coll_tier_quant").read() >= q_before + 1
    exact_b, wire_b = pallas_quant.wire_stats(n, np.float32, nranks)
    assert wire_b <= 0.3 * exact_b
    assert mpit.pvar("dev_coll_quant_bytes_saved").read() >= \
        s_before + (exact_b - wire_b)
    rel = np.abs(got["quant"] - exp).max() / np.abs(exp).max()
    assert rel <= budget, rel

    # exact mode: cvar unset, same call is bit-identical to XLA
    _reload(MV2T_QUANT_COLL=None, MV2T_DEV_TIER_VMEM_MAX=None,
            MV2T_DEV_TIER_QUANT_MIN=None)

    def app_exact(comm):
        out = comm.allreduce(data[comm.rank])
        if comm.rank == 0:
            got["exact"] = np.asarray(out)

    run_ranks(nranks, app_exact, device_mesh=True)
    import jax as _jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = _jax.devices()[:nranks]
    mesh = make_mesh((nranks,), ("x",), devs)
    x = _jax.device_put(
        jnp.asarray(data.reshape(-1)),
        NamedSharding(mesh, P("x")))
    from mvapich2_tpu.parallel.mesh import shard_map
    ref = _jax.jit(shard_map(
        lambda s: _jax.lax.psum(s, "x"), mesh=mesh,
        in_specs=(P("x"),), out_specs=P("x"), check_vma=False))(x)
    np.testing.assert_array_equal(
        got["exact"], np.asarray(ref).reshape(nranks, n)[0])


# ---------------------------------------------------------------------------
# the lint ratchet: the new module is covered by the device pass
# (seeded-violation test per the PR 12 convention)
# ---------------------------------------------------------------------------

def test_device_pass_covers_pallas_quant(tmp_path):
    """Dropping a wait from the quantized streamer's issue path is a
    device-pass finding — the new kernel module sits under the same
    DMA-discipline ratchet as ops/pallas_ici.py."""
    import os as _os

    from mvapich2_tpu.analysis import core
    from mvapich2_tpu.analysis.device import DevicePass
    src_path = _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "mvapich2_tpu", "ops",
        "pallas_quant.py")
    src = open(src_path).read()
    # the committed module is clean
    mods, errs = core.scan_paths([src_path])
    assert not errs
    assert DevicePass(profiles=[]).run(mods) == []
    # (a) drop the stage-load wait: the encode reads a chunk the DMA
    # may not have landed
    mut = src.replace("        ld.wait()\n        # fold the bytes",
                      "        # fold the bytes")
    assert mut != src
    p = tmp_path / "pallas_quant_mut.py"
    p.write_text(mut)
    mods2, _ = core.scan_paths([str(p)])
    fs = DevicePass(profiles=[]).run(mods2)
    assert any("'ld'" in f.msg and "without a matching wait" in f.msg
               for f in fs), [f.msg for f in fs]
