"""The harness: one process that binds the cell's ranks to the chip(s),
warms the cell's one shape, offers its traffic for ``--seconds`` through
the library's front door, and reduces what it timed, traced and counted
to the metrics BENCHMARK.json lists for the cell.

Everything that belongs to one cell is data found by name (see
README.md): the cell in BENCHMARK.json, its configuration under
``configs/``, its traffic under ``traffic/``, its collective under
``collectives/``, each per-layer metric under ``layer_metrics/``.
From the program it takes ``run_ranks``, ``comm.<collective>``, the
pvars, the recorder's spans and the compile-cache directory.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from . import breakdown, check, generator, stats, xplane
from .context import DeviceTrace, RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, ".trace")        # listed in .gitignore
TRACED_TAIL_S = 2.0         # the traced sub-window: the window's last seconds
ITER_MARK = "chipbench_iter"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No TPU, or another number of chips than the cell asks for."""


def say(msg: str) -> None:
    print(msg, flush=True)


def read_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} called {name!r}")


def load_by_name(folder: str, name: str):
    """The module ``chipbench/<folder>/<name>.py``, whatever characters
    a metric's or collective's name holds."""
    path = os.path.join(HERE, folder, name + ".py")
    mod_name = f"chipbench.{folder}." + re.sub(r"\W", "_", name)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"{folder}/{name}.py is not there")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(cell_name: str):
    """BENCHMARK.json, the cell's entry in it, and the cell's
    configuration, traffic mix and collective, each found by name."""
    bench = read_json(ROOT, "BENCHMARK.json")
    cell = by_name(bench["workloads"], cell_name, "workload")
    config = read_json(ROOT, by_name(bench["configs"], cell["config"],
                                     "configuration")["file"])
    traffic = read_json(HERE, "traffic", cell["traffic"] + ".json")
    if traffic["loop"] not in generator.LOOPS:
        raise KeyError(f"no loop of kind {traffic['loop']!r}")
    if config["chips"] != cell["chips"]:
        raise ValueError(f"{cell_name}: the cell asks for {cell['chips']} "
                         f"chips, its configuration for {config['chips']}")
    coll = load_by_name("collectives", traffic["collective"])
    return bench, cell, config, traffic, coll


def reported_in(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


class CompileCounter:
    """Counts what jax compiles, or loads from its persistent cache,
    from now on: ``seen`` holds every compile and cache event by name as
    [count, seconds]; COMPILE_EVENT counts the backend compilations. One
    listener for the life of the process."""

    _installed: Optional["CompileCounter"] = None

    def __init__(self):
        self.seen: Dict[str, List[float]] = {}

    def _note(self, name: str, secs: float = 0.0) -> None:
        if name.startswith(("/jax/core/compile", "/jax/compilation_cache")):
            got = self.seen.setdefault(name, [0, 0.0])
            got[0] += 1
            got[1] += secs

    @classmethod
    def installed(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax.monitoring
            me = cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(
                lambda name, secs, **_kw: me._note(name, secs))
            jax.monitoring.register_event_listener(
                lambda name, **_kw: me._note(name))
        return cls._installed

    def counts(self) -> Dict[str, int]:
        return {name: int(c) for name, (c, _s) in self.seen.items()}

    def summary(self) -> str:
        return "; ".join(
            f"{name.rsplit('/', 1)[-1]} x{int(c)}" + (f" {s:.3f} s" if s else "")
            for name, (c, s) in sorted(self.seen.items()))


@dataclass
class Rehearsal:
    """How chipbench/tests drives a run without a chip: a size that the
    CPU holds and the mesh to bind. Never reachable from the command."""
    bytes_per_rank: int
    device_mesh: object = None          # None: the configuration's own
    peaks_kind: str = "TPU v5 lite"
    traced_tail_s: float = 0.3


@dataclass
class Shared:
    """What the rank threads leave for the main thread."""
    ranks: int
    inputs: List = field(default_factory=list)
    warm: List = field(default_factory=list)
    last: List = field(default_factory=list)
    lat_s: List = field(default_factory=list)
    wait_s: List = field(default_factory=list)
    spans: Dict[int, list] = field(default_factory=dict)
    devices: List = field(default_factory=list)
    off_device: int = 0
    stop_after: Optional[int] = None
    failed_iters: set = field(default_factory=set)
    attempted: int = 0
    t_first: float = 0.0                # perf_counter at the first timed call
    t_end: float = 0.0                  # perf_counter when the last rank ended
    to_mono: float = 0.0                # time.monotonic minus perf_counter
    trace_from: Optional[int] = None    # first traced iteration
    iter_mono: Dict[int, float] = field(default_factory=dict)
    iter_wait: List = field(default_factory=list)   # rank 0, traced: (t1, t2)
    stamps: Dict[str, float] = field(default_factory=dict)  # set-up phases
    events_before: Dict[str, int] = field(default_factory=dict)
    events_after: Dict[str, int] = field(default_factory=dict)
    cache_before: int = 0
    cache_after: int = 0
    lock: threading.Lock = field(default_factory=threading.Lock)

    def __post_init__(self):
        for name in ("inputs", "warm", "last", "lat_s", "wait_s", "devices"):
            setattr(self, name, [None] * self.ranks)


def _pvar_reads(names) -> Dict[str, int]:
    from mvapich2_tpu import mpit
    return {n: int(mpit.pvar(n).read()) for n in names}


def _fallback_names() -> List[str]:
    from mvapich2_tpu import mpit
    names = (mpit.pvar_get_info(i)["name"] for i in range(mpit.pvar_get_num()))
    return [n for n in names if n.startswith("dev_coll_fallback_")]


def _bind_mesh(config: dict, rehearsal: Optional[Rehearsal]):
    if rehearsal is not None and rehearsal.device_mesh is not None:
        return rehearsal.device_mesh
    spec = config["front_door"]["device_mesh"]
    if spec is True:
        return True
    import jax
    from mvapich2_tpu.parallel.mesh import make_mesh
    n = int(np.prod(spec["shape"]))
    return make_mesh(tuple(spec["shape"]), tuple(spec["axes"]),
                     jax.devices()[:n])


def _read_back(sh: Shared, into: List, rank: int, out, dev) -> None:
    """Outside any timing: one result to the host, and whether it lived
    on the rank's own device."""
    into[rank] = np.asarray(out)
    if out.devices() != {dev}:
        with sh.lock:
            sh.off_device += 1


def _rank_app(comm, *, sh: Shared, coll, traffic: dict, config: dict,
              seed: int, seconds: float, nelems: int, dtype, trace: bool,
              traced_tail_s: float, cache_dir: str, compiles: CompileCounter):
    """One rank's whole run: data, warm-up, the window, read-back."""
    import jax
    from mvapich2_tpu.utils.compile_cache import cache_entries
    rank = comm.rank
    ch = comm.device_channel
    want = config["expect"]["channel"]
    if type(ch).__name__ != want:
        raise RuntimeError(f"rank {rank} is bound to {type(ch).__name__}, "
                           f"the configuration says {want}")
    dev = sh.devices[rank] = ch.device
    x_host = sh.inputs[rank] = generator.make_input(traffic, seed, rank,
                                                      nelems, dtype)
    x = jax.block_until_ready(jax.device_put(x_host, dev))
    if rank == 0:
        sh.stamps["data on the device"] = time.perf_counter()

    for w in range(int(traffic["warmup_calls"])):
        out = jax.block_until_ready(coll.call(comm, x))
        if w == 0:
            if rank == 0:
                sh.stamps["first call (compile or cache load)"] = \
                    time.perf_counter()
            _read_back(sh, sh.warm, rank, out, dev)
    comm.barrier()
    if rank == 0:
        sh.cache_before = cache_entries(cache_dir)
        sh.events_before = compiles.counts()
    comm.barrier()

    lat, wait = [], []
    marks = trace and rank == 0
    i = 0
    t_begin = time.perf_counter()
    to_mono = sh.to_mono = time.monotonic() - t_begin
    with sh.lock:       # the window opens when its first rank starts
        sh.t_first = min(sh.t_first or t_begin, t_begin)
    while True:
        mark = None
        if marks and sh.trace_from is not None and i >= sh.trace_from:
            sh.iter_mono[i] = time.monotonic()
            mark = jax.profiler.TraceAnnotation(ITER_MARK, i=i)
            mark.__enter__()
        try:
            t0 = time.perf_counter()
            out = coll.call(comm, x)
            t1 = time.perf_counter()
            out = jax.block_until_ready(out)
            t2 = time.perf_counter()
        except BaseException:
            with sh.lock:
                sh.failed_iters.add(i)
                sh.attempted = max(sh.attempted, i + 1)
            raise
        finally:
            if mark is not None:
                mark.__exit__(None, None, None)
        lat.append(t2 - t0)
        wait.append(t2 - t1)
        if mark is not None:            # rank 0's wait, on time.monotonic
            sh.iter_wait.append((t1 + to_mono, t2 + to_mono))
        if rank == 0 and sh.stop_after is None:
            # every rank passes the collective's rendezvous in iteration
            # i + 1 only after rank 0 entered it, so all of them see
            # this before they decide whether i + 1 was the last
            elapsed = t2 - t_begin
            if elapsed >= seconds:
                sh.stop_after = i + 1
            elif (trace and sh.trace_from is None
                  and elapsed >= seconds - traced_tail_s):
                _start_trace()
                sh.trace_from = i + 1
        if sh.stop_after is not None and i >= sh.stop_after:
            break
        i += 1
    t_done = time.perf_counter()
    with sh.lock:
        sh.attempted = max(sh.attempted, i + 1)
        sh.t_end = max(sh.t_end, t_done)
    if marks and sh.trace_from is not None:
        jax.profiler.stop_trace()
    comm.barrier()
    if rank == 0:
        sh.events_after = compiles.counts()
        sh.cache_after = cache_entries(cache_dir)

    # outside the timing: the last call's result, read back
    _read_back(sh, sh.last, rank, out, dev)
    sh.lat_s[rank], sh.wait_s[rank] = lat, wait
    if trace:
        tracer = getattr(comm.u.engine, "tracer", None)
        sh.spans[rank] = list(tracer.events) if tracer is not None else []


def _start_trace() -> None:
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the host's Python frames: not read
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)


def _reduce_trace(ctx: RunContext, sh: Shared, device_ids: List[int],
                  need_devices: bool) -> None:
    """Fill the context from the trace the window's tail wrote: the
    sub-window runs from the first marked iteration's start to the last
    one's end; per device, the busy intervals inside it."""
    profile = xplane.load(xplane.newest_trace(TRACE_DIR))
    marks = xplane.annotations(profile, ITER_MARK)
    if not marks:
        raise RuntimeError(f"the trace holds no {ITER_MARK} annotation")
    lo, hi = marks[0][0], max(e for _s, e, _st in marks)
    ctx.traced_calls = len(marks)
    ctx.clock_offset_s = xplane.clock_offset(marks, sh.iter_mono)
    ctx.rank0_ordinal = device_ids[0]
    planes = xplane.device_planes(profile)
    for ordinal in sorted(set(device_ids)):
        if ordinal not in planes:
            if not need_devices:
                continue
            raise RuntimeError(
                f"the trace has no plane {xplane.DEVICE_PLANE}{ordinal}; "
                f"it has {[p.name for p in profile.planes]}")
        ops = xplane.line_events(planes[ordinal], xplane.OPS_LINE)
        busy = xplane.clip(xplane.union((s, e) for _n, s, e in ops), lo, hi)
        ctx.devices[ordinal] = DeviceTrace(ordinal, lo, hi, busy, ops)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_process: float, rehearsal: Optional[Rehearsal] = None
             ) -> dict:
    """One run of one cell; returns the result line's object. Raises
    ``NoChip`` (and prints no result) without the chips the cell asks
    for, unless ``rehearsal`` says this is a test."""
    bench, cell, config, traffic, coll = load_cell(cell_name)

    if trace:                   # before the ranks are bound: the recorder
        os.environ["MV2T_TRACE"] = "1"      # attaches when a rank starts
    else:
        os.environ.pop("MV2T_TRACE", None)
    import jax
    from mvapich2_tpu import run_ranks
    from mvapich2_tpu.utils.compile_cache import ensure_compile_cache
    from mvapich2_tpu.utils.config import get_config
    get_config().reload()
    cache_dir = ensure_compile_cache()
    t_imported = time.perf_counter()

    devs = jax.devices()
    t_devices = time.perf_counter()
    kind = devs[0].device_kind
    if rehearsal is None:
        if devs[0].platform != "tpu":
            raise NoChip(f"jax found no TPU (platform {devs[0].platform})")
        if len(devs) != cell["chips"]:
            raise NoChip(f"{cell_name} asks for {cell['chips']} chips, "
                         f"jax reports {len(devs)}")
    peaks = read_json(HERE, "peaks.json")
    peaks_kind = kind if rehearsal is None else rehearsal.peaks_kind
    if peaks_kind not in peaks:
        raise KeyError(f"peaks.json has no row for device kind {kind!r}")

    ranks = int(config["ranks"])
    dtype = np.dtype(config["dtype"])
    nbytes = int(traffic["bytes_per_rank"] if rehearsal is None
                 else rehearsal.bytes_per_rank)
    nelems = nbytes // dtype.itemsize
    level_names = list(config["expect"]["level_pvars"])
    fb_names = _fallback_names()
    level0, fb0 = _pvar_reads(level_names), _pvar_reads(fb_names)
    compiles = CompileCounter.installed()
    say(f"cell {cell_name}: {ranks} ranks x {nbytes} B {dtype} "
        f"{traffic['collective']} ({traffic['op']}), {traffic['loop']} loop, "
        f"seed {seed}, {seconds} s, trace {int(trace)} | device "
        f"{devs[0].platform} {kind} x {len(devs)} | compile cache "
        f"{cache_dir or '(none placed)'}")

    sh = Shared(ranks)
    sh.stamps.update({"imports": t_imported, "jax.devices()": t_devices})
    error = None
    try:
        app = functools.partial(
            _rank_app, sh=sh, coll=coll, traffic=traffic, config=config,
            seed=seed, seconds=seconds, nelems=nelems, dtype=dtype,
            trace=trace, cache_dir=cache_dir, compiles=compiles,
            traced_tail_s=(TRACED_TAIL_S if rehearsal is None
                           else rehearsal.traced_tail_s))
        run_ranks(ranks, app, device_mesh=_bind_mesh(config, rehearsal),
                  timeout=seconds + 1500.0)
    except (RuntimeError, TimeoutError) as e:
        error = e
        say(f"run failed: {e!r} (cause {e.__cause__!r})")
    finally:
        os.environ.pop("MV2T_TRACE", None)
        get_config().reload()

    setup_s = sh.t_first - t_process
    iterations = 0 if error else min(len(r) for r in sh.lat_s)
    calls = int(traffic["warmup_calls"]) + iterations
    t_ref = time.perf_counter()
    compared: List[check.Compared] = []
    if error is None:
        reference = coll.reference(sh.inputs)
        compared += check.compare_results("warm-up call", sh.warm, reference)
        compared += check.compare_results("last call of the window", sh.last,
                                          reference)
        del reference
    else:
        compared.append(check.Compared("run ended without an error", False,
                                       True, False))
    level1, fb1 = _pvar_reads(level_names), _pvar_reads(fb_names)
    level_rise = {n: level1[n] - level0[n] for n in level_names}
    fb_rise = {n: fb1[n] - fb0[n] for n in fb_names}
    inside = {n: c - sh.events_before.get(n, 0)
              for n, c in sh.events_after.items()
              if c != sh.events_before.get(n, 0)}
    distinct = len({str(d) for d in sh.devices if d is not None})
    compared += check.compare_counts(
        ranks, calls, level_rise, fb_rise, inside.get(COMPILE_EVENT, 0),
        sh.cache_before, sh.cache_after, sh.off_device)
    compared.append(check.Compared(
        "distinct devices the ranks live on", distinct, cell["chips"],
        rehearsal is not None or distinct == cell["chips"]))
    check.report(compared, say)
    correct = check.verdict(compared)
    say(f"reference and comparison took {time.perf_counter() - t_ref:.3f} s "
        f"(outside the window and outside setup_s)")

    used = [d for d in dict.fromkeys(sh.devices) if d is not None] or devs[:1]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    device = {"platform": devs[0].platform, "kind": kind,
              "device_kind": kind, "count": len(devs),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(sh.attempted),
              "failed": len(sh.failed_iters), "metrics": {}, "device": device}
    if error is not None:
        return result

    window_s = sh.t_end - sh.t_first
    lat_us = stats.iteration_latency_us(sh.lat_s)
    wait_us = stats.iteration_latency_us(sh.wait_s)
    say(f"window: {iterations} iterations in {window_s:.6f} s "
        f"(sum of iteration latencies {lat_us.sum() / 1e6:.6f} s); "
        f"per-rank median latency us "
        f"{[round(float(np.median(r)) * 1e6, 1) for r in sh.lat_s]}; "
        f"caller's wait in block_until_ready, max over ranks: median "
        f"{float(np.median(wait_us)):.1f} us; latency min "
        f"{lat_us.min():.1f} max {lat_us.max():.1f} us")
    at, phases = t_process, []
    for name, t in list(sh.stamps.items()) + [("warm-up, read-back, barrier",
                                               sh.t_first)]:
        phases.append(f"{name} {t - at:.3f}")
        at = t
    say("setup phases, s: " + "; ".join(phases))
    say("jax compile and cache events of the whole run: " + compiles.summary())
    say("jax compile and cache events inside the window: "
        f"{ {n.rsplit('/', 1)[-1]: c for n, c in inside.items()} or 'none'}")
    tenths = [round(float(np.median(t)), 1)
              for t in np.array_split(lat_us, 10) if len(t)]
    say(f"window tenths: median latency us of each tenth of the window "
        f"{tenths}")
    say(f"memory: peak_bytes_in_use {peak} on the fullest of {len(used)} "
        f"devices; compile cache {sh.cache_before} entries before the "
        f"window, {sh.cache_after} after; setup_s {setup_s:.3f}")

    if not trace:
        values = stats.end_to_end(lat_us, coll.bus_factor(ranks), nbytes,
                                  window_s, setup_s)
        for m in bench["end_to_end"]:
            if reported_in(m, cell_name):
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
        return result

    ctx = RunContext(collective=coll, config=config, traffic=traffic,
                     ranks=ranks, bytes_per_rank=nbytes, device_kind=kind,
                     peaks=peaks[peaks_kind],
                     window_mono=(sh.t_first + sh.to_mono,
                                  sh.t_end + sh.to_mono), spans=sh.spans,
                     caller_waits=sh.iter_wait,
                     counters={**level_rise, **fb_rise})
    if sh.trace_from is not None:
        ids = [d.id for d in sh.devices]
        _reduce_trace(ctx, sh, ids, need_devices=rehearsal is None)
        say(f"trace: {ctx.traced_calls} collectives in the traced "
            f"sub-window; clock offset (monotonic -> trace axis) "
            f"{ctx.clock_offset_s}; recorder held "
            f"{len(sh.spans.get(0, []))} events of rank 0")
    for m in bench["per_layer"]:
        if not reported_in(m, cell_name):
            continue
        value = load_by_name("layer_metrics", m["name"]).compute(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}
    if ctx.devices:
        device["busy_s"] = float(np.mean([d.busy_s
                                          for d in ctx.devices.values()]))
        device["window_s"] = ctx.rank0_device().window_s
        result["breakdown"] = {"device_ops": breakdown.device_ops(ctx),
                               "idle_gaps": breakdown.idle_gaps(ctx)}
    return result


def main(argv, t_process: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process)
    except NoChip as e:
        print(f"chipbench: {e}; nothing was measured", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0
