"""Stall watchdog: automatic hang diagnostics from inside progress_wait.

PR 1's intercomm-NBC starvation was diagnosed blind — wall clock and
aggregate pvars only. This watchdog makes the next one ship its own
post-mortem: when one progress_wait call exceeds MV2T_STALL_TIMEOUT
seconds, a ONE-SHOT diagnostic (per engine) is emitted to the mlog
stream and latched on the engine:

    * the debugger.py message-queue snapshot (posted / unexpected /
      pending-send queues),
    * outstanding requests tracked by the engine,
    * active NBC schedules (remaining / in-flight vertices),
    * the last MV2T_STALL_EVENTS trace events (when tracing is on).

Independent of MV2T_TRACE: the queue/request/schedule sections come from
live engine state, so the watchdog works untraced; the event tail is the
only tracing-gated section. Default off (0.0) so tests that legitimately
block never spam; env-settable for production runs.
"""

from __future__ import annotations

import time
from typing import Optional

from .. import mpit
from ..utils.config import cvar, get_config
from ..utils.mlog import get_logger

log = get_logger("watchdog")

cvar("STALL_TIMEOUT", 0.0, float, "trace",
     "Seconds one progress_wait may block before the stall watchdog "
     "emits its one-shot diagnostic (0 = off; default off in tests).")
cvar("STALL_EVENTS", 64, int, "trace",
     "How many trailing trace events the stall diagnostic includes "
     "(only when MV2T_TRACE is on).")

_pv_trips = mpit.pvar("stall_watchdog_trips", mpit.PVAR_CLASS_COUNTER,
                      "trace", "stall-watchdog diagnostics emitted "
                      "(one-shot per progress engine)")


def configure(engine) -> None:
    """Arm (or disarm) the watchdog on ``engine`` from the cvar registry
    — called from Universe.initialize after the config reload, so the
    hot path only ever checks the cached ``_stall_limit`` attribute."""
    limit = float(get_config().get("STALL_TIMEOUT", 0.0) or 0.0)
    engine._stall_limit = limit if limit > 0 else None
    engine._stall_tripped = False


def build_report(engine) -> str:
    """Assemble the diagnostic text from live engine state. Safe to call
    from the stalled waiter: progress_wait holds no engine mutex at its
    sleep point, and every section takes the mutex itself."""
    lines = [f"# stall watchdog, world rank {engine.rank}: progress_wait "
             f"exceeded {getattr(engine, '_stall_limit', 0)}s"]

    u = getattr(engine, "universe", None)
    if u is not None and getattr(u, "protocol", None) is not None:
        from ..debugger import dump_message_queues
        try:
            lines.append(dump_message_queues(u).format())
        except Exception as e:   # diagnostics must never kill the waiter
            lines.append(f"## message queues unavailable: {e!r}")
    else:
        lines.append("## message queues unavailable (no universe bound)")

    with engine.mutex:
        reqs = list(engine.outstanding.values())
        lines.append(f"## outstanding requests ({len(reqs)})")
        for req in reqs[:32]:
            lines.append(f"  {req!r}")
        nbc = getattr(engine, "nbc", None)
        scheds = list(nbc.active) if nbc is not None else []
    lines.append(f"## active NBC schedules ({len(scheds)})")
    for st in scheds[:16]:
        lines.append(f"  {st.req.kind}: {st.remaining} vertices remaining, "
                     f"in-flight={sorted(st.inflight)} "
                     f"ready={sorted(st.ready)}")

    lockcheck = getattr(engine, "_lockcheck", None)
    if lockcheck is not None:
        lines.append(lockcheck.report())

    # failure-containment forensics: which peer went dark, and at which
    # flat-protocol step. A deadline trip's report names the stale lease
    # (age vs MV2T_PEER_TIMEOUT) and dumps per-slot seq numbers + fold
    # epoch + poison flag for every comm on the flat tier, so a wedged
    # wave reads as "slot 3 never stamped in_seq 17" instead of a blind
    # stall.
    pch = getattr(u, "plane_channel", None) if u is not None else None
    if pch is not None:
        fmap = _field_map()
        try:
            lines.append("## peer liveness leases (node-local, timeout "
                         f"{getattr(pch, '_peer_timeout', 0)}s)"
                         f"{_region_tag(fmap, 'lease')}")
            for ln in pch.lease_report():
                lines.append(f"  {ln}")
        except Exception as e:
            lines.append(f"## peer leases unavailable: {e!r}")
        try:
            lines.extend(_flat_report(u, pch, fmap))
        except Exception as e:
            lines.append(f"## flat-slot state unavailable: {e!r}")
        # native trace tail of EVERY co-located rank (MV2T_NTRACE ring,
        # region-tagged): the hang report shows the last C-plane events
        # — which flat phase each rank reached, who rang whose bell,
        # whether a lease scan fired — not just counter values
        try:
            from . import native as _native
            n = int(get_config().get("STALL_EVENTS", 64))
            lines.append("## native C-plane trace tail (per local rank)")
            for ln in _native.tail_lines(pch, n):
                lines.append(f"  {ln}")
        except Exception as e:
            lines.append(f"## native trace tail unavailable: {e!r}")
        lines.extend(_protocol_map_lines(fmap))
        # control-plane forensics: a job wedged BEFORE the datapath —
        # mid-wire, mid-claim, waiting on a bootstrap card — shows up
        # here as "stage 1, 2 peers bell-less, wire deadline in 83s"
        # instead of a blind stall
        try:
            lines.extend(_control_report(pch))
        except Exception as e:
            lines.append(f"## control-plane state unavailable: {e!r}")

    # device-lane forensics: a rank wedged inside a device collective
    # hangs in the rendezvous or inside a Mosaic kernel whose
    # outstanding copy/semaphore state is invisible from the host — the
    # report names the tier the job has been running, the rendezvous
    # barrier occupancy, and the static copy/semaphore protocol map the
    # mv2tlint device pass builds (which pending containers and credit
    # semaphores the kernel can be stuck on).
    if u is not None:
        try:
            lines.extend(_device_report(u))
        except Exception as e:   # diagnostics must never kill the waiter
            lines.append(f"## device-lane state unavailable: {e!r}")

    tracer = getattr(engine, "tracer", None)
    if tracer is not None:
        n = int(get_config().get("STALL_EVENTS", 64))
        tail = tracer.tail(n)
        lines.append(f"## last {len(tail)} trace events")
        for ts, layer, name, ph, args in tail:
            lines.append(f"  {ts:.6f} [{layer}] {name} {ph}"
                         f"{' ' + repr(args) if args else ''}")
        # conformance over the tail: replay the window through the
        # protocol automata (truncation-safe invariants only) and name
        # the first violated invariant — a hang with a poisoned flat
        # region or an un-pumped NBC schedule says so here instead of
        # leaving the reader to eyeball the event list
        try:
            from ..analysis import conform
            rank = getattr(engine, "rank", -1)
            viols = conform.check_tail(
                rank if isinstance(rank, int) else -1, tail,
                options={"peer_timeout": float(
                    get_config().get("PEER_TIMEOUT", 0.0) or 0.0)})
            if viols:
                v = viols[0]
                lines.append(f"## trace-tail conformance: "
                             f"{len(viols)} violation(s), first is "
                             f"{v.automaton}/{v.invariant}: {v.message}")
            else:
                lines.append("## trace-tail conformance: no invariant "
                             "violated in the tail window (stall is "
                             "likely a liveness wait, not a protocol "
                             "break)")
        except Exception as e:   # diagnostics must never kill the waiter
            lines.append(f"## trace-tail conformance unavailable: {e!r}")
    return "\n".join(lines)


def _device_report(u) -> list:
    """Device-lane hang section: live channel/rendezvous state plus the
    static lane map (pending containers + credit semaphores) harvested
    by the mv2tlint device pass — the device analog of the shared-field
    protocol map below. Empty when no device channel is bound."""
    ch = getattr(getattr(u, "comm_world", None), "device_channel", None)
    if ch is None:
        return []
    lines = [f"## device-lane state ({type(ch).__name__}, "
             f"rank {ch.rank}/{ch.size})"]
    rv = getattr(ch, "rv", None)
    if rv is not None:
        gate = rv.gate
        lines.append(f"  rendezvous: {gate.n_waiting}/{rv.size} ranks "
                     f"waiting, broken={gate.broken}")
        # the derived communicators this rank is a member of: who waits
        # at which gate, by context id
        for sub in rv.live(ch.world[ch.rank]):
            ctx, world = sub.key
            lines.append(f"  derived rendezvous ctx {ctx} (world ranks "
                         f"{list(world)}): {sub.gate.n_waiting}/{sub.size} "
                         f"ranks waiting, broken={sub.gate.broken}")
    try:
        pvs = []
        for name in ("dev_coll_tier_vmem", "dev_coll_tier_hbm",
                     "dev_coll_tier_quant",
                     "dev_coll_quant_bytes_saved",
                     "dev_coll_fallback_size", "dev_coll_fallback_dtype",
                     "dev_coll_fallback_host_dtype",
                     "dev_coll_fallback_host_comm", "dev_coll_derived",
                     "dev_coll_fallback_shape",
                     "dev_coll_fallback_platform"):
            v = mpit.pvar(name).read()
            if v:
                pvs.append(f"{name}={v:g}")
        lines.append("  tier counters: " + (" ".join(pvs) or "(none)"))
        rma = []
        for name in ("dev_rma_tier_rdma", "dev_rma_tier_quant",
                     "dev_rma_tier_epoch", "dev_rma_flush",
                     "dev_rma_wire_bytes",
                     "dev_rma_fallback_noncontig",
                     "dev_rma_fallback_platform",
                     "dev_rma_fallback_size", "dev_rma_fallback_dtype"):
            v = mpit.pvar(name).read()
            if v:
                rma.append(f"{name}={v:g}")
        if rma:
            lines.append("  one-sided counters: " + " ".join(rma))
    except Exception:
        pass
    lines.extend(device_map_lines())
    return lines


def _control_report(pch) -> list:
    """Live control-plane section: per-peer wiring stage, daemon claim
    epoch + manifest version, the in-flight wire-gate deadline — then
    the static key/state map the mv2tlint proto pass harvests."""
    wired = getattr(pch, "_wired", None)
    stage = getattr(pch, "_wire_stage", None)
    lines = [f"## control-plane state (wired={wired}, "
             f"wire stage={stage})"]
    bells = getattr(pch, "_peer_bells", {}) or {}
    for w in getattr(pch, "local_ranks", []):
        if w == pch.my_rank:
            continue
        lines.append(f"  peer {w}: bell "
                     f"{'set' if w in bells else 'UNSET'}"
                     f"{' [C-ABI]' if w in pch.cabi_ranks else ''}")
    dl = getattr(pch, "_wire_deadline", 0.0)
    if not wired and dl:
        lines.append(f"  in-flight KVS wait: wire gate, deadline in "
                     f"{max(0.0, dl - time.monotonic()):.1f}s "
                     "(MV2T_WIRE_TIMEOUT)")
    try:
        from ..runtime import boot as bootmod
        from ..runtime.daemon import MANIFEST_VERSION
        b = bootmod.current_boot()
        cl = getattr(b, "daemon_claim", None) if b is not None else None
        if cl is not None:
            lines.append(f"  daemon claim: set {cl.setkey} epoch "
                         f"{cl.epoch} (manifest v{MANIFEST_VERSION})")
    except Exception:
        pass
    try:
        from .. import mpit
        active = mpit.pvar("daemon_claims_active").read()
        waits = mpit.pvar("daemon_queue_waits").read()
        hits = mpit.pvar("exec_cache_hits").read()
        misses = mpit.pvar("exec_cache_misses").read()
        if active or waits or hits or misses:
            lines.append(f"  daemon: claims active {active:g}, queue "
                         f"waits {waits:g}; exec-cache {hits:g} hit / "
                         f"{misses:g} miss "
                         f"({mpit.pvar('exec_cache_bytes').read():g} B "
                         "written)")
    except Exception:
        pass
    lines.extend(proto_map_lines())
    return lines


def proto_map_lines(max_keys: int = 24) -> list:
    """The static control-plane protocol map (KVS key families +
    wire states + version constants) harvested by the mv2tlint proto
    pass — shared by this report and ``mpistat --proto-map``."""
    try:
        from ..analysis.proto import proto_state_map
        m = proto_state_map()
    except Exception:
        m = {}
    if not m:
        return ["## control-plane protocol map unavailable (proto "
                "sources not parseable)"]
    lines = ["## control-plane protocol map (mv2tlint proto pass)"]
    ws = m.get("wire_states", {})
    if ws:
        lines.append("  wire states: " + "  ".join(
            f"{k} @ {v['module'].rsplit('/', 1)[-1]}:{v['line']}"
            for k, v in sorted(ws.items())))
    for name, ver in sorted(m.get("versions", {}).items()):
        lines.append(f"  version constant: {name} = {ver}")
    keys = m.get("keys", {})
    lines.append(f"  kvs key families ({len(keys)}; write/read sites):")
    for i, (fam, info) in enumerate(sorted(keys.items())):
        if i >= max_keys:
            lines.append(f"    ... ({len(keys) - max_keys} more)")
            break
        lines.append(f"    {fam}: {info['writes']}w/{info['reads']}r "
                     f"({', '.join(info['modules'])})")
    return lines


def device_map_lines() -> list:
    """The static device-lane protocol map, one line per pending
    container / credit semaphore — shared by this report and
    ``mpistat --device-map``."""
    try:
        from ..analysis.device import device_lane_map
        lane = device_lane_map()
    except Exception:
        lane = {}
    if not lane:
        return ["## device-lane protocol map unavailable (device "
                "sources not parseable)"]
    lines = ["## device-lane protocol map (mv2tlint device pass)"]
    for name, info in sorted(lane.items()):
        if info["kind"] == "pending-map":
            kind = "remote" if info["remote"] else "local"
            lines.append(
                f"  pending-map {name} [{kind}] drains="
                f"{','.join(info['drains']) or '-'} ({info['module']})")
        else:
            lines.append(
                f"  credit-sem {name} signals={info['signals']} "
                f"waits={info['waits']} ({info['module']})")
    return lines


def _field_map() -> dict:
    """The mv2tlint native pass's shared-field map ({word: kind/region/
    site}), parsed from the C sources' ``shared:`` annotations. The map
    is what lets a hang report NAME the protocol region (seqlock flat
    wave / liveness lease / doorbell) a stuck wait belongs to instead
    of printing bare word dumps. Diagnostics must never kill the
    waiter, so any parse trouble degrades to an empty map."""
    try:
        from ..analysis.native import shared_field_map
        return shared_field_map()
    except Exception:
        return {}


def _region_tag(fmap: dict, word: str) -> str:
    """`` [atomic(lease)]``-style tag for a shared word, or ''."""
    info = fmap.get(word)
    if not info:
        return ""
    reg = info.get("region")
    return f" [{info['kind']}({reg})]" if reg else f" [{info['kind']}]"


def _protocol_map_lines(fmap: dict) -> list:
    """One summary section mapping every annotated shared word to its
    protocol region, grouped by (kind, region)."""
    if not fmap:
        return ["## shared-field protocol map unavailable (native "
                "annotations not parseable)"]
    by_region = {}
    for name, info in sorted(fmap.items()):
        # counter regions are free-text rationales — don't splay them
        reg = "-" if info["kind"] == "counter" \
            else (info.get("region") or "-")
        key = (info["kind"], reg)
        by_region.setdefault(key, []).append(name)
    lines = ["## shared-field protocol map (mv2tlint native pass)"]
    for (kind, reg), names in sorted(by_region.items()):
        lines.append(f"  {kind}({reg}): {', '.join(names)}")
    return lines


def _flat_report(u, pch, fmap=None) -> list:
    """Per-comm flat-slot region state (slots' in/out seqs, fold epoch,
    poison flag) for every live comm with flat-tier state, each word
    tagged with its protocol region from the shared-field map."""
    lines = []
    fmap = fmap or {}
    seq_tag = _region_tag(fmap, "fl_in")
    lib = pch._ring.lib
    if not pch.plane:
        return lines
    import ctypes as ct
    shown = 0
    for ctx, comm in sorted(u.comms_by_ctx.items()):
        st = comm.__dict__.get("_flat_state")
        if shown >= 8:
            lines.append("  ... (more comms elided)")
            break
        if st is None:
            continue
        if st is False:
            lines.append(f"## flat region for {comm.name} (ctx {ctx}): "
                         "POISONED/closed for this comm")
            shown += 1
            continue
        if getattr(st, "tier", 1) == 2:
            # hierarchical tier: region wave counter + per-group and
            # leaders-exchange slot seqs (wedged waves name which level
            # stalled: a lagging group slot = intra-fold, a lagging
            # leaders slot = leader exchange)
            f2tag = _region_tag(fmap, "fl2_mseq")
            poi = lib.cp_flat2_poisoned(pch.plane, st.ctx, st.lane)
            base = lib.cp_flat2_base(pch.plane, st.ctx, st.lane)
            k = lib.cp_flat2_group()
            lines.append(f"## flat2 region {comm.name} (ctx {st.ctx}, "
                         f"lane {st.lane}, k={k}): mseq={base} "
                         f"poison={bool(poi)} local_seq={st.base + st.k}"
                         f"{f2tag}")
            i = ct.c_longlong()
            o = ct.c_longlong()
            ngroups = (st.size + k - 1) // k
            for g in range(ngroups):
                gn = min(k, st.size - g * k)
                for slot in range(gn):
                    if lib.cp_flat2_slot_state(pch.plane, st.ctx,
                                               st.lane, g, slot,
                                               i, o) == 0:
                        lines.append(f"  g{g} slot {slot}: "
                                     f"in_seq={i.value} "
                                     f"out_seq={o.value}{f2tag}")
            for g in range(ngroups):
                if lib.cp_flat2_slot_state(pch.plane, st.ctx, st.lane,
                                           8, g, i, o) == 0:
                    lines.append(f"  leaders slot {g}: in_seq={i.value} "
                                 f"out_seq={o.value}{f2tag}")
            shown += 1
            continue
        poi = lib.cp_flat_poisoned(pch.plane, st.ctx, st.lane)
        base = lib.cp_flat_base(pch.plane, st.ctx, st.lane)
        lines.append(f"## flat region {comm.name} (ctx {st.ctx}, lane "
                     f"{st.lane}): fold epoch/bseq={base} "
                     f"poison={bool(poi)} local_seq={st.base + st.k}"
                     f"{seq_tag}")
        i = ct.c_longlong()
        o = ct.c_longlong()
        for slot in range(st.size):
            if lib.cp_flat_slot_state(pch.plane, st.ctx, st.lane, slot,
                                      i, o) == 0:
                lines.append(f"  slot {slot}: in_seq={i.value} "
                             f"out_seq={o.value}{seq_tag}")
        if lib.cp_flat_slot_state(pch.plane, st.ctx, st.lane,
                                  lib.cp_flat_nslots(), i, o) == 0:
            lines.append(f"  bcast block: bseq={i.value} "
                         f"last_nbytes={o.value}{seq_tag}")
        shown += 1
    return lines


def trip(engine) -> Optional[str]:
    """One-shot diagnostic for ``engine`` (no-op after the first trip —
    a hung job would otherwise emit one report per backoff cycle)."""
    if getattr(engine, "_stall_tripped", False):
        return None
    engine._stall_tripped = True
    _pv_trips.inc()
    report = build_report(engine)
    engine._stall_report = report
    log.warn("%s", report)
    if (tr := getattr(engine, "tracer", None)) is not None:
        tr.record("progress", "stall_watchdog_trip", "i",
                  t=time.monotonic())
    return report
