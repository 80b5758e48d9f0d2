#!/usr/bin/env python
"""acceptance — capture the BASELINE.md acceptance configs as one JSON
artifact (a single 64 MiB point leaves regressions off that point
invisible).

Five configs (BASELINE.md "Acceptance configs"):
  1. osu_allreduce f32, 8 ranks, 4 B..4 MiB  (CPU host channel)
  2. bcast + allgather over a device mesh
  3. alltoall + reduce_scatter over a device mesh (MoE shuffle)
  4. 3D 7-pt stencil halo exchange (halo_exchange/ppermute)
  5. hierarchical 2-level allreduce (intra-node shm + inter-node)
plus a TPU HBM slot-allreduce size sweep when a TPU is attached (the
north-star path at more than one point).

Each config runs in its own subprocess (its own JAX platform env), so
the rank-based configs stay on CPU while the sweep config can own the
TPU. Aggregate artifact: --out, or acceptance.json in the working
directory.

Usage:
    python benchmarks/acceptance.py               # all configs
    python benchmarks/acceptance.py --quick       # smaller sizes
    python benchmarks/acceptance.py --config mesh_bcast   # (internal)
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# ---------------------------------------------------------------- helpers

def _parse_osu_table(out: str):
    """OSU table -> [{size, lat_us}]; lines are '<size> <avg us> ...'."""
    pts = []
    for ln in out.splitlines():
        m = re.match(r"\s*(\d+)\s+([0-9.]+)", ln)
        if m:
            pts.append({"size": int(m.group(1)),
                        "lat_us": float(m.group(2))})
    return pts


def _mpirun_bench(np_, prog, args, extra_env=None, fake_nodes=None,
                  timeout=900):
    cmd = [sys.executable, "-m", "mvapich2_tpu.run", "-np", str(np_)]
    if fake_nodes:
        cmd += ["--fake-nodes", fake_nodes]
    cmd += [sys.executable, os.path.join(REPO, "benchmarks", prog), *args]
    env = dict(os.environ)
    # one process per chip: this parent never imports jax, and the
    # launcher plus its rank children are forced onto the CPU
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if extra_env:
        env.update(extra_env)
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=timeout)
    if r.returncode != 0:
        return None, f"rc={r.returncode}: {r.stdout[-400:]} {r.stderr[-400:]}"
    return _parse_osu_table(r.stdout), None


def _mesh8():
    """An 8-device mesh: real devices if >=8, else virtual CPU devices
    (the subprocess env already forced JAX_PLATFORMS=cpu +
    xla_force_host_platform_device_count=8 for mesh configs)."""
    import jax
    from jax.sharding import Mesh
    import numpy as np
    devs = jax.devices()
    n = 8 if len(devs) >= 8 else len(devs)
    return Mesh(np.array(devs[:n]), ("x",)), jax.devices()[0].platform, n


def _time_op(fn, x, iters=10, skip=2):
    import jax
    for _ in range(skip):
        jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


# ------------------------------------------------------------- mesh configs

def run_mesh_coll(kind: str, quick: bool):
    """bcast/allgather/alltoall/reduce_scatter over an 8-device mesh
    via the framework's MeshComm (acceptance configs 2 + 3)."""
    import jax
    import jax.numpy as jnp
    from mvapich2_tpu.parallel.mesh import MeshComm, shard_map
    from jax.sharding import PartitionSpec as P

    mesh, platform, n = _mesh8()
    comm = MeshComm(mesh)
    top = 1 << (20 if quick else 22)
    pts = []
    size = 4096
    while size <= top:
        nel = max(size // 4, n)  # per-shard f32 elements ~ `size` bytes
        x = jnp.ones((n * nel,), jnp.float32)

        body = {
            "bcast": lambda s: comm.bcast(s, root=0),
            "allgather": lambda s: comm.all_gather(s, tiled=True),
            "alltoall": lambda s: comm.all_to_all(
                s.reshape(n, -1), split_axis=0, concat_axis=0),
            "reduce_scatter": lambda s: comm.reduce_scatter(s),
        }[kind]
        out_spec = P(None) if kind == "allgather" else P("x")
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x"),),
                              out_specs=out_spec, check_vma=False))
        t = _time_op(f, x)
        pts.append({"size": size, "lat_us": round(t * 1e6, 2)})
        size *= 4
    return {"points": pts, "platform": platform, "devices": n}


def run_stencil_cfg(quick: bool):
    """config 4: 3D 7-pt stencil halo exchange on the mesh."""
    from mvapich2_tpu.parallel.mesh import MeshComm
    from mvapich2_tpu.models.stencil import run_stencil
    import jax

    mesh, platform, n = _mesh8()
    comm = MeshComm(mesh)
    grid = 64 if quick else 128
    iters = 4
    # warm (compile)
    jax.block_until_ready(run_stencil(comm, grid=grid, iters=iters))
    t0 = time.perf_counter()
    jax.block_until_ready(run_stencil(comm, grid=grid, iters=iters))
    dt = (time.perf_counter() - t0) / iters
    return {"grid": grid, "iters": iters, "platform": platform,
            "devices": n, "step_ms": dt * 1e3,
            "cells_per_s": grid ** 3 / dt}


def run_tpu_hbm_sweep(quick: bool):
    """North-star path at multiple sizes: the HBM slot-segment
    allreduce (ops/pallas_hbm) swept 1..64 MiB on the real chip."""
    import jax
    if jax.devices()[0].platform == "cpu":
        return {"skipped": "no TPU attached"}
    import jax.numpy as jnp
    from mvapich2_tpu.ops import pallas_hbm as ph
    from mvapich2_tpu.utils.slopetime import slope, wrap_repeat

    R = 8
    pts = []
    for mib in ([1, 16] if quick else [1, 4, 16, 64]):
        m = mib << 20
        M = m // 512           # (M, R, 128) f32 interleaved slots
        bufs = jnp.ones((M, R, 128), jnp.float32)
        # the two-point slope needs (k2-k1)*t_op well above dispatch
        # noise: small sizes use a much longer chain
        k1, k2 = (4, 16) if mib >= 64 else (8, 96)
        best = None
        for name, op, traffic, chains in ph.bench_candidates(M, R):
            fn_k = wrap_repeat(op, chains)
            try:
                t = slope(fn_k, bufs, k1=k1, k2=k2, iters=6, skip=2,
                          nrep=3)
            except Exception:
                continue
            if t <= 1e-8:      # slope lost in noise: not a real number
                continue
            if best is None or t < best[1]:
                best = (name, t)
        if best is None:
            return {"error": "no candidate ran"}
        name, t = best
        eff = 2 * R * m / t / 1e9  # reference reduce+bcast convention
        pts.append({"size": m, "algo": name,
                    "eff_GBps": round(eff, 2),
                    "t_op_ms": round(t * 1e3, 4)})
    return {"points": pts, "platform": "tpu", "emu_ranks": R}


MESH_ENV = {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}


def run_config(name: str, quick: bool):
    if name == "mesh_bcast":
        return run_mesh_coll("bcast", quick)
    if name == "mesh_allgather":
        return run_mesh_coll("allgather", quick)
    if name == "mesh_alltoall":
        return run_mesh_coll("alltoall", quick)
    if name == "mesh_reduce_scatter":
        return run_mesh_coll("reduce_scatter", quick)
    if name == "stencil":
        return run_stencil_cfg(quick)
    if name == "tpu_hbm_sweep":
        return run_tpu_hbm_sweep(quick)
    raise ValueError(name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--config", help="(internal) run one config inline")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()

    if a.config:
        print(json.dumps(run_config(a.config, a.quick)))
        return 0

    results = {}
    mx = "1048576" if a.quick else "4194304"
    it = "20" if a.quick else "50"

    # 1. CPU-channel allreduce, 8 ranks, 4 B..4 MiB
    pts, err = _mpirun_bench(8, "osu_allreduce.py",
                             ["-m", mx, "-i", it, "-x", "3"])
    results["cpu_allreduce_8rank"] = (
        {"points": pts, "channel": "shm"} if pts else {"error": err})

    # 5. 2-level: 2 fake nodes x 4 ranks (shm intra + tcp inter)
    pts, err = _mpirun_bench(8, "osu_allreduce.py",
                             ["-m", mx, "-i", it, "-x", "3"],
                             fake_nodes="0,0,0,0,1,1,1,1")
    results["twolevel_allreduce_2x4"] = (
        {"points": pts, "channel": "2level shm+tcp"} if pts
        else {"error": err})

    # 2-4 + TPU sweep: each in its own subprocess with its own platform
    for cfg in ["mesh_bcast", "mesh_allgather", "mesh_alltoall",
                "mesh_reduce_scatter", "stencil", "tpu_hbm_sweep"]:
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        if cfg != "tpu_hbm_sweep":
            env.update(MESH_ENV)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--config", cfg]
        if a.quick:
            cmd.append("--quick")
        try:
            # one process per chip: this parent never imports jax; the
            # mesh configs' children are forced onto the CPU (MESH_ENV)
            # and tpu_hbm_sweep's child is the only process that takes
            # the chip, one child at a time
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env=env, timeout=1200)
            line = r.stdout.strip().splitlines()[-1] if r.stdout.strip() \
                else ""
            results[cfg] = json.loads(line) if r.returncode == 0 and line \
                else {"error": f"rc={r.returncode}: {r.stderr[-300:]}"}
        except (subprocess.TimeoutExpired, json.JSONDecodeError) as e:
            results[cfg] = {"error": str(e)[:300]}
        print(f"[acceptance] {cfg}: "
              f"{'ok' if 'error' not in results[cfg] else results[cfg]['error'][:120]}",
              file=sys.stderr, flush=True)

    out = a.out or os.path.abspath("acceptance.json")
    with open(out, "w") as f:
        json.dump({"quick": a.quick, "configs": results}, f, indent=1)
    print(json.dumps({"written": out,
                      "ok": [k for k, v in results.items()
                             if "error" not in v],
                      "failed": [k for k, v in results.items()
                                 if "error" in v]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
