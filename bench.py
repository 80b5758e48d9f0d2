"""Driver benchmark: osu_allreduce over the ICI device path.

Measurement contract mirrors the OSU harness (BASELINE.md:
osu_allreduce.c:110-142): warm-up skips, timed iterations, bus bandwidth
via the ring model busbw = 2*(p-1)/p * m / t.

Adaptations for this environment:
  * On a multi-chip host this times lax.psum over a mesh of all real
    devices (ICI). On a single chip (no wire for an allreduce to cross)
    it times the device phase of the framework's single-chip collective:
    the HBM slot-segment reduce (ops/pallas_hbm.py, the kernel behind
    coll/device.py:HBMSlotChannel — the path mpirun-on-one-chip ranks
    take). 8 rank-buffers deposited in an HBM slot segment are reduced
    in one fused pallas pass; the broadcast is zero-copy (every rank's
    result is a view of the shared result slot, as with the reference's
    shm slotted segment — ch3_shmem_coll.c:527). Device traffic is R*m
    read + m written — the information floor for the reduction. As in
    r1/r2, host-side deposit/readback are outside the timed region (the
    OSU contract reuses registered buffers across iterations; the slot
    segment is likewise persistent).
  * The candidate set (slot-reduce at two block sizes, the materialized
    broadcast variant, the XLA fallback) comes from
    ops/pallas_hbm.bench_candidates — the bench-time form of the tuning
    layer's measured-crossover discipline. Reported ``value`` is the
    *effective* bandwidth normalized to the reference reduce+bcast
    traffic (2*R*m / t, the convention for algorithmically-improved
    collectives: a fixed logical volume over the measured completion
    time), so the baseline target 0.8*raw-HBM is unchanged from r1/r2;
    ``detail.actual_hbm_GBps`` reports the physical traffic rate, which
    cannot exceed the HBM roofline.
  * Per-op time is derived by the two-point slope method: run the op
    K1 and K2 times inside one jitted program (a scalar readback ends
    each — `block_until_ready` and a readback both wait for the device
    here), t_op = (T(K2) - T(K1)) / (K2 - K1), which cancels the
    constant dispatch + readback cost of a call. Pallas calls are
    opaque to XLA (and the slot-reduce candidates are marked effectful)
    so the repeated calls cannot be algebraically collapsed; the XLA
    candidate uses lax.fori_loop for the same reason. Timing is
    min-of-iters (constant overhead + positive noise), slope is
    median-of-5.

A device benchmark: with no TPU it exits non-zero and prints no result
(a CPU timing is never written under a device metric's name), and a
candidate that fails to compile or run fails the whole run — no
candidate is skipped in favour of the XLA one. What this script
measures is due for redefinition (ROADMAP A1).

Prints exactly ONE JSON line.
"""

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SKIP = 3
ITERS = 12
K1, K2 = 4, 16
# 64 MiB float32 per rank is the north-star point; MV2T_BENCH_BYTES
# shrinks it (rounded up to the 512-byte granularity of the emulated
# (m/512, 8, 128) layout so the bandwidth formula matches the bytes
# actually moved)
MSG_BYTES = max(512, int(os.environ.get("MV2T_BENCH_BYTES",
                                        64 * 1024 * 1024)) // 512 * 512)
EMU_RANKS = 8


def _sz_label() -> str:
    if MSG_BYTES % (1024 * 1024) == 0:
        return f"{MSG_BYTES // (1024 * 1024)}MiB"
    if MSG_BYTES % 1024 == 0:
        return f"{MSG_BYTES // 1024}KiB"
    return f"{MSG_BYTES}B"


def _slope(fn_k, x, nrep=5):
    """Median-of-nrep two-point slopes (cancels dispatch+readback);
    shared harness, bench's iteration counts."""
    from mvapich2_tpu.utils.slopetime import slope
    return slope(fn_k, x, k1=K1, k2=K2, iters=ITERS, skip=SKIP,
                 nrep=nrep)


def _emulated_candidates(M):
    """(name, fn_k, traffic_bytes) candidates for the 1-chip allreduce
    on the interleaved (M, 8, 128) f32 slot array. Framework ops from
    ops/pallas_hbm plus the plain XLA reduction."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mvapich2_tpu.utils.slopetime import wrap_repeat

    m = M * 128 * 4
    from mvapich2_tpu.ops import pallas_hbm as ph
    cands = [(name, wrap_repeat(op, chains), traffic)
             for name, op, traffic, chains in ph.bench_candidates(
                 M, EMU_RANKS)]

    # the plain XLA reduction: fori_loop so the chain isn't
    # algebraically collapsed
    def xla_body(a):
        s = a.sum(axis=1, keepdims=True) * (1.0 / EMU_RANKS)
        return jnp.broadcast_to(s, a.shape)

    @functools.partial(jax.jit, static_argnums=1)
    def xla_fn(v, k):
        out = lax.fori_loop(0, k, lambda _, a: xla_body(a), v)
        return jnp.sum(out[:64, 0, 0])

    cands.append(("xla_sum_bcast", xla_fn, 2 * EMU_RANKS * m))
    return cands


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mvapich2_tpu.parallel import MeshComm, make_mesh
    from mvapich2_tpu.utils.compile_cache import ensure_compile_cache
    from mvapich2_tpu.utils.detect import detect

    ensure_compile_cache()
    info = detect()
    if info.platform != "tpu":
        sys.exit(f"bench.py measures the device path and needs a TPU; "
                 f"jax found {info.platform!r} — no result")
    devices = jax.devices()
    p = len(devices)
    n_f32 = MSG_BYTES // 4

    if p > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mvapich2_tpu import ops as mops
        from mvapich2_tpu.parallel.mesh import shard_map
        comm = MeshComm(make_mesh((p,), ("x",), devices))
        x = jax.device_put(
            jnp.ones((p * n_f32,), jnp.float32),
            NamedSharding(comm.mesh, P("x")))

        def mk_fn(body):
            def spmd(v, k):
                out = lax.fori_loop(0, k, lambda _, a: body(a), v)
                return lax.psum(jnp.sum(out[:8]), "x")

            @functools.partial(jax.jit, static_argnums=1)
            def fn_k(v, k):
                f = shard_map(spmd, mesh=comm.mesh,
                              in_specs=(P("x"), None), out_specs=P(),
                              check_vma=False)
                return f(v, k)
            return fn_k

        # candidates: XLA's fused psum lowering vs the explicit
        # ppermute ring (MPIR_Allreduce_pt2pt_ring_MV2 form) vs the
        # HBM-streaming chunked remote-DMA ring (ops/pallas_ici — the
        # engine behind the large-message device tier) — the
        # measured-crossover discipline of the tuning layer
        from mvapich2_tpu.ops import pallas_ici
        cands = [
            ("xla_psum",
             mk_fn(lambda a: lax.psum(a, "x") * (1.0 / p))),
            ("ring_manual",
             mk_fn(lambda a: mops.ring_allreduce_manual(a, "x")
                   * (1.0 / p))),
            ("ici_ring_hbm",
             mk_fn(lambda a: pallas_ici.hbm_ring_all_reduce(a, "x", p)
                   * (1.0 / p))),
        ]
        best_t, chosen = None, None
        for name, fn_k in cands:
            t = _slope(fn_k, x)     # a candidate that fails, fails the run
            if best_t is None or t < best_t:
                best_t, chosen = t, name
        t_op = best_t
        ranks = p
        raw_gbps = info.ici_bw_gbps
        target = 0.8 * raw_gbps
        m = MSG_BYTES
        # the OSU ring busbw model: each rank's NIC moves 2(p-1)/p * m
        value = 2.0 * (ranks - 1) / ranks * m / t_op / 1e9
        metric = (f"osu_allreduce_busbw_{_sz_label()}_f32"
                  f"[ici,p={ranks}]")
        detail_extra = {}
    else:
        M = n_f32 // 128
        x = jax.random.normal(jax.random.PRNGKey(0), (M, 8, 128),
                              jnp.float32)
        best_t, chosen, chosen_traffic = None, None, None
        for name, fn_k, traffic in _emulated_candidates(M):
            t = _slope(fn_k, x)     # a candidate that fails, fails the run
            if best_t is None or t < best_t:
                best_t, chosen, chosen_traffic = t, name, traffic
        t_op = best_t
        ranks = EMU_RANKS
        raw_gbps = info.hbm_bw_gbps
        target = 0.8 * raw_gbps
        m = MSG_BYTES
        # effective bandwidth: the reference reduce+bcast traffic
        # (read R*m + write R*m) over the measured completion time of
        # the framework's collective (which may move fewer bytes — the
        # zero-copy slot broadcast)
        value = 2.0 * ranks * m / t_op / 1e9
        metric = (f"osu_allreduce_effbw_{_sz_label()}_f32"
                  f"[hbm(1chip-emulated),emu_ranks={ranks}]")
        detail_extra = {
            "traffic_bytes_per_op": chosen_traffic,
            "actual_hbm_GBps": round(chosen_traffic / t_op / 1e9, 1),
            "traffic_model": ("slot-reduce, zero-copy bcast (R*m read + "
                              "m written)" if "slot" in (chosen or "")
                              else "materialized bcast (R*m read + R*m "
                              "written)"),
        }

    print(json.dumps({
        "metric": metric,
        "value": round(value, 3),
        "unit": "GB/s",
        "vs_baseline": round(value / target, 4),
        "device": {"platform": info.platform, "kind": info.device_kind,
                   "count": p},
        "detail": {
            "device": info.device_kind,
            "devices": p,
            "algo": chosen,
            "t_op_ms": round(t_op * 1e3, 3),
            "target_GBps(0.8*raw)": round(target, 1),
            "slope_window": [K1, K2],
            "iters": ITERS, "skip": SKIP,
            **detail_extra,
        },
    }))


if __name__ == "__main__":
    main()
