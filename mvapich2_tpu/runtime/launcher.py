"""mpirun — the process launcher.

Analog of mpirun_rsh/mpispawn (SURVEY §3.6, /root/reference/src/pm/mpirun/):
parse -np/-hostfile-ish args, start the KVS service (the PMI tree analog),
spawn one OS process per rank with the bootstrap env, forward stdio, and
reap exit codes — killing the job if any rank dies (the launcher-driven
failure detection of SURVEY §5.3).

Single-host only for now; ranks map to TPU work through the device mesh,
not through multi-host ssh trees (multi-host uses jax.distributed's own
coordinator when available).
"""

from __future__ import annotations

import argparse
import os
import re
import signal
import subprocess
import sys
import threading
import time
from typing import List, Optional

from .childenv import cpu_rank_env

from .kvs import KVSServer


def _abort_exit_code(aborted: Optional[str], default: int = 1) -> int:
    """Exit code for an MPI_Abort-ed job: the errorcode travels in the
    abort event, not the aborting rank's exit status (the launcher's
    kill can beat that rank to its own os._exit — mpirun_rsh likewise
    propagates the code out-of-band). Codes that can't be an exit
    status (<=0, >=256) degrade to the generic failure code."""
    m = re.search(r"MPI_Abort\((-?\d+)\)", aborted or "")
    code = int(m.group(1)) if m else default
    return code if 0 < code < 256 else 1


def _kill_all(procs: List[subprocess.Popen]) -> None:
    """SIGTERM, grace period, SIGKILL stragglers."""
    for p in procs:
        if p.poll() is None:
            p.terminate()
    time.sleep(0.2)
    for p in procs:
        if p.poll() is None:
            p.kill()


def launch(nranks: int, argv: List[str], env_extra: Optional[dict] = None,
           fake_nodes: Optional[List[int]] = None,
           timeout: Optional[float] = None, ft: bool = False) -> int:
    """Run ``argv`` as ``nranks`` rank processes; returns max exit code.

    ``ft=False`` (default): a rank dying with nonzero status kills the job
    (mpirun_rsh cleanup-on-abnormal-exit behavior). ``ft=True`` (the
    ``mpiexec -disable-auto-cleanup`` analog): ANY nonzero rank death —
    signal or error exit — is published to the KVS as a failure event, so
    survivors blocked on that peer unwind with MPIX_ERR_PROC_FAILED and
    can revoke/shrink (SURVEY §5.3; the reference's ft suite kills ranks
    with exit(1), test/mpi/ft/senddead.c:30). Error exits additionally
    surface in the job's exit code (max positive code over all ranks) —
    publication gives ULFM visibility, it does not mask the error."""
    # MPIEXEC_ALLOW_FAULT (the MPICH faults-suite contract,
    # errors/faults/testlist.in): simulated rank deaths are EXPECTED —
    # publish them as failure events (so survivors unwind with
    # MPIX_ERR_PROC_FAILED instead of hanging) and exclude them from
    # the job's exit code; success = some rank completed cleanly.
    allow_fault = str((env_extra or {}).get(
        "MPIEXEC_ALLOW_FAULT",
        os.environ.get("MPIEXEC_ALLOW_FAULT", ""))).lower() \
        in ("1", "yes", "true")
    if allow_fault:
        ft = True
    srv = KVSServer(nranks)
    procs: List[subprocess.Popen] = []
    # a soft kill of the launcher must take the rank children with it —
    # an orphaned rank spins in the progress loop forever (mpirun_rsh
    # cleanup-on-signal behavior; SIGKILL needs a process group instead)
    prev_term = signal.getsignal(signal.SIGTERM)

    def _on_term(signum, frame):
        _kill_all(procs)
        raise SystemExit(128 + signum)

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:
        pass    # not the main thread: caller owns signal handling
    try:
        for r in range(nranks):
            env = dict(os.environ)
            env["MV2T_RANK"] = str(r)
            env["MV2T_SIZE"] = str(nranks)
            env["MV2T_KVS"] = srv.address
            if ft:
                env["MV2T_FT"] = "1"
            if fake_nodes is not None:
                env["MV2T_FAKE_NODE"] = f"fakenode{fake_nodes[r]}"
            if env_extra:
                env.update(env_extra)
            # rank processes must not grab the TPU: host runtime is CPU-side
            cpu_rank_env(env,
                         explicit="JAX_PLATFORMS" in (env_extra or {}))
            procs.append(subprocess.Popen(argv, env=env))
        deadline = time.monotonic() + timeout if timeout else None
        exit_codes: List[Optional[int]] = [None] * nranks
        failed: List[int] = []   # ranks published as failure events
        n_events = 0
        while any(c is None for c in exit_codes):
            for i, p in enumerate(procs):
                if exit_codes[i] is None:
                    exit_codes[i] = p.poll()
            if srv.state.aborted is not None:
                # MPI_Abort broadcast through the KVS: kill the whole
                # job at once (even in FT mode — §8.7 overrides ULFM
                # survivability; the aborting rank asked for teardown)
                print(f"mv2t-launch: {srv.state.aborted}",
                      file=sys.stderr)
                _kill_all(procs)
                codes = [p.wait() for p in procs]
                if re.search(r"MPI_Abort\(", srv.state.aborted or ""):
                    return _abort_exit_code(srv.state.aborted)
                pos = [c for c in codes if c > 0]
                return max(pos) if pos else 1
            bad = [i for i, c in enumerate(exit_codes)
                   if c is not None and c != 0 and i not in failed]
            if ft:
                for i in bad:
                    failed.append(i)
                    srv.publish(f"__failure_ev_{n_events}", str(i))
                    n_events += 1
            elif bad:
                _kill_all(procs)
                return max(c or 0 for c in exit_codes if c is not None) or 1
            if deadline and time.monotonic() > deadline:
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                raise TimeoutError(f"job exceeded {timeout}s")
            time.sleep(0.01)
        if allow_fault:
            # faults are part of the test: the job succeeds when any
            # rank finished cleanly (errors/faults/pt2ptf1.c survivors
            # print the verdict)
            return 0 if any(c == 0 for c in exit_codes) else 1
        if ft:
            # error exits count against the job even when published as
            # failure events; a job in which NO rank completed cleanly
            # (all died by signal) must still fail
            app_err = [c for c in exit_codes if c is not None and c > 0]
            if app_err:
                return max(app_err)
            return 0 if any(c == 0 for c in exit_codes) else 1
        return max(c or 0 for c in exit_codes)
    finally:
        try:
            signal.signal(signal.SIGTERM, prev_term)
        except ValueError:
            pass
        for p in procs:
            if p.poll() is None:
                p.kill()
        srv.shutdown()


def _node_is_local(name: str) -> bool:
    """Emulated node names (no DNS entry) and this host's own names run
    the agent as a local subprocess; resolvable foreign names go over
    ssh (the mpirun_rsh remote-start path)."""
    import socket
    if name in ("localhost", "127.0.0.1", socket.gethostname()):
        return True
    try:
        addr = socket.gethostbyname(name)
    except OSError:
        return True    # unresolvable = emulated node on this host
    try:
        local_addrs = {ai[4][0] for ai in socket.getaddrinfo(
            socket.gethostname(), None)}
    except OSError:
        local_addrs = set()
    return addr in local_addrs | {"127.0.0.1"}


def launch_tree(nranks: int, argv: List[str], hostfile_path: str,
                env_extra: Optional[dict] = None,
                timeout: Optional[float] = None, ft: bool = False,
                policy: str = "block") -> int:
    """Multi-node launch through per-node mpispawn agents (the
    mpirun_rsh -> mpispawn tree, src/pm/mpirun/mpispawn_tree.c analog,
    two-level). Each agent starts its node's rank processes with the
    node identity in the bootstrap env, so node_ids — and with them the
    shm intra-node channel and the two-level collectives' inter-leader
    TCP phase — follow the hostfile placement."""
    import json as _json
    import socket

    from .hostfile import map_ranks, parse_hostfile
    hosts = parse_hostfile(hostfile_path)
    mapping = map_ranks(hosts, nranks, policy)
    total_slots = sum(h.slots for h in hosts)
    if nranks > total_slots:
        print(f"mpirun: oversubscribing {nranks} ranks onto "
              f"{total_slots} slots", file=sys.stderr)
    by_node: dict = {}
    for r, h in mapping:
        by_node.setdefault(h, []).append(r)

    any_remote = any(not _node_is_local(n) for n in by_node)
    srv = KVSServer(nranks, host=socket.gethostname() if any_remote
                    else "127.0.0.1")
    agents: List[subprocess.Popen] = []
    try:
        for node, ranks in by_node.items():
            spec = {"node": node, "ranks": ranks, "size": nranks,
                    "kvs": srv.address, "argv": argv,
                    "env": env_extra or {}, "ft": ft}
            cmd = [sys.executable, "-m", "mvapich2_tpu.runtime.mpispawn",
                   _json.dumps(spec)]
            if _node_is_local(node):
                # the agent is host-runtime only: it must never reach
                # for the chip its parent or a rank may hold
                agent_env = dict(os.environ)
                agent_env["JAX_PLATFORMS"] = "cpu"
                agents.append(subprocess.Popen(cmd, env=agent_env))
            else:
                import shlex
                agents.append(subprocess.Popen(
                    ["ssh", "-o", "BatchMode=yes", node,
                     " ".join(shlex.quote(c) for c in cmd)]))
        deadline = time.monotonic() + timeout if timeout else None
        rcs: List[Optional[int]] = [None] * len(agents)
        nodes = list(by_node)
        # agent protocol consumption (runtime/mpispawn.py publishes
        # these): __agent_up_<node> distinguishes "ssh/boot failed
        # before any rank started" from "ranks ran and failed", and
        # __agent_exit_<node> carries the per-rank exit map for the
        # failure diagnostic — without reading them a dead agent is a
        # bare nonzero rc with no indication whether its node ever
        # joined the job
        agents_up: set = set()
        exit_reports: dict = {}
        while any(c is None for c in rcs):
            for i, a in enumerate(agents):
                if rcs[i] is None:
                    rcs[i] = a.poll()
            for node in nodes:
                if node not in agents_up \
                        and srv.peek(f"__agent_up_{node}") is not None:
                    agents_up.add(node)
                if node not in exit_reports:
                    raw = srv.peek(f"__agent_exit_{node}")
                    if raw:
                        try:
                            exit_reports[node] = _json.loads(raw)
                        except ValueError:
                            exit_reports[node] = {}
            if srv.state.aborted is not None:
                # MPI_Abort: tear the whole tree down (agents SIGTERM
                # their rank processes); propagate the abort errorcode
                print(f"mv2t-launch: {srv.state.aborted}",
                      file=sys.stderr)
                _stop_agents(agents)
                # an aborted job is never a success — same rule as the
                # single-host path
                return _abort_exit_code(srv.state.aborted)
            bad = [c for c in rcs if c is not None and c != 0]
            if bad and not ft:
                for i, c in enumerate(rcs):
                    if c is not None and c != 0:
                        node = nodes[i]
                        if node not in agents_up:
                            print(f"mpirun: agent for node {node} died "
                                  f"(rc {c}) before starting any rank "
                                  "— ssh/boot failure?", file=sys.stderr)
                        elif node in exit_reports:
                            print(f"mpirun: node {node} rank exits: "
                                  f"{exit_reports[node]}",
                                  file=sys.stderr)
                _stop_agents(agents)
                return max(bad)
            if any(c is not None and c < 0 for c in rcs):
                # a dead agent orphans its ranks: abort the job
                _stop_agents(agents)
                return 1
            if deadline and time.monotonic() > deadline:
                _stop_agents(agents)
                raise TimeoutError(f"job exceeded {timeout}s")
            time.sleep(0.02)
        return max(c or 0 for c in rcs)
    finally:
        _stop_agents(agents)
        srv.shutdown()


def _stop_agents(agents: List[subprocess.Popen]) -> None:
    """SIGTERM first — the agent's handler kills its rank processes —
    then SIGKILL stragglers after a grace period (a straight kill() would
    orphan every rank on the node)."""
    live = [a for a in agents if a.poll() is None]
    for a in live:
        a.terminate()
    if live:
        time.sleep(0.3)
    for a in agents:
        if a.poll() is None:
            a.kill()


def launch_vpod(nranks: int, argv: List[str],
                timeout: Optional[float] = None) -> int:
    """Virtual-pod mode: N rank *threads* in one process, COMM_WORLD bound
    to the jax devices, so collectives take the device path
    (coll/device.py). This is the single-controller execution model of a
    TPU pod slice.

    The ranks run in THIS process on whatever ``jax.devices()`` gives —
    N chips bind 1:1, fewer bind the fold or the slot channel
    (``bind_universes`` serves every geometry) — with no child: one
    process owns a chip, so a launcher that started a child after
    touching jax would lock it out. The single exception is a caller
    whose environment asks for the CPU backend: the launcher has not
    touched jax yet, and re-execs itself once onto a virtual N-device
    CPU mesh (the test-suite recipe).

    ``argv`` must be a python program (leading interpreter token is
    stripped); it runs per rank thread with mpi.Init() resolving to the
    thread's pre-bound universe."""
    prog = list(argv)
    if prog and os.path.basename(prog[0]).startswith("python"):
        prog = prog[1:]
    if not prog:
        print("mpirun --vpod: need a python script", file=sys.stderr)
        return 2

    from ..utils.detect import env_asks_for_cpu
    if env_asks_for_cpu() and not os.environ.get("MV2T_VPOD_CHILD"):
        # CPU asked for: a virtual nranks-device mesh needs XLA_FLAGS
        # set before jax initializes, hence the re-exec. The child is
        # pinned to the CPU too, so it cannot want a chip.
        env = dict(os.environ)
        env["MV2T_VPOD_CHILD"] = "1"
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={nranks}"
        ).strip()
        cmd = [sys.executable, "-m", "mvapich2_tpu.run", "-np", str(nranks),
               "--vpod"] + (["--timeout", str(timeout)] if timeout else []) \
            + argv
        return subprocess.run(cmd, env=env).returncode

    import runpy
    import traceback

    from .universe import local_universe, set_universe
    universes = local_universe(nranks, device_mesh=True)
    saved_argv, sys.argv = sys.argv, prog
    codes: List[int] = [0] * nranks

    def body(r: int) -> None:
        set_universe(universes[r])
        try:
            runpy.run_path(prog[0], run_name="__main__")
        except SystemExit as e:
            codes[r] = int(e.code or 0) if not isinstance(e.code, str) else 1
        except BaseException:   # noqa: BLE001 — rank error = job error
            traceback.print_exc()
            codes[r] = 1
        finally:
            if codes[r] != 0:
                # a failing rank (exception OR sys.exit(nonzero)) must
                # release peers blocked in collectives
                ch = getattr(universes[r].comm_world, "device_channel",
                             None)
                if ch is not None:
                    ch.abort()   # break the device-collective rendezvous
                for u in universes:
                    u.engine.wakeup()
            set_universe(None)

    threads = [threading.Thread(target=body, args=(r,), daemon=True,
                                name=f"vpod-rank-{r}")
               for r in range(nranks)]
    for t in threads:
        t.start()
    try:
        for t in threads:
            t.join(timeout)
            if t.is_alive():
                print(f"mpirun --vpod: {t.name} hung past {timeout}s",
                      file=sys.stderr)
                return 1
    finally:
        sys.argv = saved_argv   # in-process callers (chip_smoke) go on
    return max(codes)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="mpirun",
        description="mvapich2-tpu process launcher (mpirun_rsh analog)")
    ap.add_argument("-np", "-n", type=int, default=1, dest="np")
    ap.add_argument("--fake-nodes", type=str, default=None,
                    help="comma-separated fake node id per rank "
                         "(emulate multi-node on one host)")
    ap.add_argument("--ft", "--disable-auto-cleanup", action="store_true",
                    dest="ft", help="fault-tolerant mode: dead ranks become "
                    "failure events instead of killing the job (ULFM)")
    ap.add_argument("--vpod", action="store_true",
                    help="virtual-pod mode: rank threads bound to a device "
                         "mesh; collectives take the XLA/ICI path")
    ap.add_argument("--hostfile", "-f", default=None,
                    help="multi-node launch: one mpispawn agent per host "
                         "(unresolvable names = emulated nodes here)")
    ap.add_argument("--map", choices=("block", "cyclic"), default="block",
                    help="rank->host mapping policy for --hostfile")
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no command given")
    if args.vpod:
        return launch_vpod(args.np, args.command, timeout=args.timeout)
    rm_tmp = None
    if not args.hostfile and not args.fake_nodes:
        # inside a multi-node resource-manager allocation (Slurm/PBS),
        # adopt its node list as the hostfile (src/pm/mpirun slurm/pbs
        # adapters; runtime/rm.py). --fake-nodes/--hostfile take
        # precedence: explicit placement beats the allocation.
        from .rm import rm_hosts
        hosts = rm_hosts()
        if hosts and len(hosts) > 1:
            import tempfile
            fd, rm_tmp = tempfile.mkstemp(suffix=".hosts",
                                          prefix="mv2t-rm-")
            with os.fdopen(fd, "w") as hf:
                for h in hosts:
                    hf.write(f"{h.name} slots={h.slots}\n")
            print(f"mpirun: using {len(hosts)}-node allocation from the "
                  f"resource manager", file=sys.stderr)
            args.hostfile = rm_tmp
    if args.hostfile:
        try:
            return launch_tree(args.np, args.command, args.hostfile,
                               timeout=args.timeout, ft=args.ft,
                               policy=args.map)
        finally:
            if rm_tmp is not None:
                try:
                    os.unlink(rm_tmp)
                except OSError:
                    pass
    fake = None
    if args.fake_nodes:
        fake = [int(x) for x in args.fake_nodes.split(",")]
        if len(fake) != args.np:
            ap.error("--fake-nodes length must equal -np")
    return launch(args.np, args.command, fake_nodes=fake,
                  timeout=args.timeout, ft=args.ft)


if __name__ == "__main__":
    sys.exit(main())
