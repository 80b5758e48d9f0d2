"""One tier rule (ISSUE 45): the tier a device collective counts is the
tier its program lowered to is the kernel that ran. ``pallas_ici.
planned_tier`` is asked by the lowering (``ici_all_reduce`` /
``ici_all_gather`` / ``ici_reduce_scatter``) and by the channel's
per-call accounting (``_decide_tier``), so for every reduction op, under
and over the VMEM edge, on the 1:1 mesh channel and on the fold channel
at two ranks a chip, four witnesses agree: the filed plan's ``tier``, the
``dev_coll_tier_*`` pvar the call bumped, the ``tier`` of its
``dev_<coll>`` span and of the trace-time ``ici_<coll>`` instant, and the
engine whose wrapper the program's trace entered. The kernels run under
the interpreter on four CPU devices. max / min / prod under the edge fail
at the parent: counted ``vmem``, run by ``mv2t_hbm_all_reduce``."""

import jax
import numpy as np
import pytest

import plain_reference as ref
from mvapich2_tpu import mpit
from mvapich2_tpu.core import op as opmod
from mvapich2_tpu.ops import pallas_ici, pallas_ring
from mvapich2_tpu.parallel.mesh import make_mesh
from mvapich2_tpu.runtime.universe import run_ranks
from mvapich2_tpu.utils.config import get_config

P4 = 4
VMEM_MAX = 8192
OPS = {"sum": opmod.SUM, "max": opmod.MAX, "min": opmod.MIN,
       "prod": opmod.PROD}
# binding -> (ranks over the four devices, the channel that binds them)
BINDINGS = {"mesh": (4, "DeviceCollChannel"),
            "fold_k2": (8, "DeviceFoldChannel")}
# case -> (collective, op, the instant its lowering drops, whether the
# flat VMEM ring can carry it)
CASES = {"allreduce_sum": ("allreduce", "sum", "ici_allreduce", True),
         "allreduce_max": ("allreduce", "max", "ici_allreduce", False),
         "allreduce_min": ("allreduce", "min", "ici_allreduce", False),
         "allreduce_prod": ("allreduce", "prod", "ici_allreduce", False),
         "reduce": ("reduce", "sum", "ici_allreduce", True),
         "allgather": ("allgather", None, "ici_allgather", True),
         "reduce_scatter_block": ("reduce_scatter_block", "sum",
                                  "ici_reduce_scatter", False)}
# float32 elements a rank: what the rule keys on (the shard, or the
# gather's output) at or under the edge, and over it
ELEMS = {"under": {"allgather": 128}, "over": {"allgather": 4096}}
ENGINES = {"vmem": (pallas_ring, ("ring_all_reduce", "ring_all_gather")),
           "hbm": (pallas_ici, ("hbm_ring_all_reduce", "hbm_ring_all_gather",
                                "hbm_ring_reduce_scatter"))}


@pytest.fixture
def interpreted(monkeypatch):
    """Every size takes the device, traced; the ring kernels run under
    the interpreter with the VMEM edge at 8 KiB and no XLA crossover."""
    for k, v in {"MV2T_DEVICE_COLL_MIN_BYTES": "1", "MV2T_ICI_INTERPRET": "1",
                 "MV2T_DEV_TIER_VMEM_MAX": str(VMEM_MAX),
                 "MV2T_DEV_TIER_XLA_MIN": "-1", "MV2T_TRACE": "1"}.items():
        monkeypatch.setenv(k, v)
    get_config().reload()
    yield
    monkeypatch.undo()
    get_config().reload()


@pytest.fixture
def entered(monkeypatch):
    """The engines whose wrappers a program's trace entered, in order."""
    seen = []
    for tier, (mod, names) in ENGINES.items():
        for name in names:
            def spy(*a, _sound=getattr(mod, name), _tier=tier, **kw):
                seen.append(_tier)
                return _sound(*a, **kw)
            monkeypatch.setattr(mod, name, spy)
    return seen


def _tier_pvars():
    return {t: mpit.pvar(f"dev_coll_tier_{t}").read()
            for t in ("vmem", "hbm", "quant")}


@pytest.mark.parametrize("binding", list(BINDINGS))
@pytest.mark.parametrize("size", list(ELEMS))
@pytest.mark.parametrize("case", list(CASES))
def test_counted_tier_is_lowered_tier_is_kernel_called(interpreted, entered,
                                                       case, size, binding):
    name, op, instant, flat_ring = CASES[case]
    ranks, klass = BINDINGS[binding]
    n = ELEMS[size].get(name, 1024 if size == "under" else 4096)
    keyed = n * 4 * (ranks if name == "allgather" else 1)
    assert (keyed <= VMEM_MAX) == (size == "under")
    want_tier = "vmem" if size == "under" and flat_ring else "hbm"
    lim = 2 if op == "prod" else 1 << 20
    data = [np.random.default_rng([45, r]).integers(
        -lim, lim, size=n, endpoint=True).astype(np.float32)
        for r in range(ranks)]
    got, plans, lanes = [None] * ranks, [None] * ranks, [None] * ranks
    calls = 2

    def app(comm):
        ch = comm.device_channel
        assert type(ch).__name__ == klass
        x = jax.device_put(data[comm.rank], ch.device)
        kw = {} if op is None else {"op": OPS[op]}
        if name == "reduce":
            kw["root"] = 1
        for _ in range(calls):
            out = getattr(comm, name)(x, **kw)
            if out is not None:
                out = jax.block_until_ready(out)
        got[comm.rank] = None if out is None else np.asarray(out)
        plans[comm.rank] = [p.tier for p in ch._plans.values()]
        lanes[comm.rank] = [e for e in comm.u.engine.tracer.events
                            if e[1] == "device"]

    before = _tier_pvars()
    run_ranks(ranks, app,
              device_mesh=make_mesh((P4,), ("x",), jax.devices()[:P4]))
    rose = {t: v - before[t] for t, v in _tier_pvars().items()}

    want = (ref.reduce(data, 1, op) if name == "reduce"
            else ref.allgather(data) if name == "allgather"
            else getattr(ref, name)(data, op))
    for r in range(ranks):
        assert (got[r] is None) == (want[r] is None), r
        if want[r] is not None:
            assert np.array_equal(got[r], want[r]), (case, r)
    # the kernel: the one program of the call entered one engine, once
    assert entered == [want_tier], entered
    # counted: a rank a call, the deciding call and the planned one
    assert rose == {t: ranks * calls * (t == want_tier) for t in rose}, rose
    for r in range(ranks):
        assert plans[r] == [want_tier], (r, plans[r])
        begun = [a["tier"] for _t, _l, nm, ph, a in lanes[r]
                 if nm == f"dev_{name}" and ph == "B"]
        assert begun == [want_tier] * calls, (r, begun)
    # lowered: the instant the dispatcher dropped while rank 0 traced
    lowered = [a["tier"] for lane in lanes for _t, _l, nm, ph, a in lane
               if nm == instant and ph == "i"]
    assert lowered == [want_tier], lowered
