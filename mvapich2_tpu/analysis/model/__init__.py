"""Shm-protocol model checker.

The native datapath's three lock-free protocols — the seqlock flat-wave
collective (cplane.cpp cp_flat_*), the adaptive doorbell wait/wake
(ShmChannel + cp_wait_quantum), and the liveness-lease failure detector
— re-expressed as small interleaved state machines, explored
exhaustively (bounded) by ``explorer.explore``. The mv2tlint ``native``
pass proves the C sources USE the atomic idioms; this package proves
the PROTOCOLS those idioms implement are actually safe under every
interleaving the memory model allows:

  * no-torn-read-delivered + agreement  (seqlock.build_allreduce)
  * poison stickiness across ctx reuse  (seqlock.build_allreduce crash=)
  * fan-in-first bcast numbering        (seqlock.build_bcast)
  * no lost wakeup                      (doorbell.build)
  * death detected within 2x timeout,
    clean departure never a failure     (lease.build)

The device lane rides the same net: ``ici.build_ring`` models the
chunk-credit flow control of the HBM-streaming remote-DMA engine
(ops/pallas_ici.py) — exhaustively, where an interpreter or chip run
sees one interleaving — proving no-slot-collision, no-lost-credit, agreement and
deadlock freedom for uni- and bidirectional rings under the
global-chunk-counter slot schedule. Its ``quant=True`` variant models
the block-quantized wire (ops/pallas_quant.py: scale word + packed
codes per chunk, dequant-fold at consume): same slot/credit schedule
over the shrunken wire chunks, with agreement tightened to "every
delivered chunk decodes with its sender's scale word" and the
``scale_after_payload`` split-landing break seeded against it.
``ici.build_alltoallv`` extends the net to the MoE-shaped alltoallv
wire (ops/pallas_alltoall.py): per-peer VARIABLE chunk counts on the
global-counter slot schedule with per-step credit waves and full-size
padding chunks — its seeded breaks (slot derived from the local
valid-chunk tally under skew, credit re-grant skipped on a zero-count
peer's padding) are each caught by a named invariant.

The one-sided lane (ops/pallas_rma.py + rma/device.py) adds
``rma.build_passive``: the passive-target epoch — MPI_Win_lock, C
accumulate chunks through the D-credit slot schedule, flush's
completion wave, unlock — against a concurrent local reader at the
target and the two-phase target fold (operand capture + commit store).
It proves lock exclusivity, no torn window read under concurrent
Put + local load, flush-completes-all-outstanding, and per-element
accumulate atomicity; its five seeded breaks (flush one chunk short,
unlock before the completion wave, fold operand prefetch racing the
previous commit, lock-bypassing local load, exclusivity-ignoring
acquire) are each caught by a named invariant.

The CONTROL plane (the one protocol surface PRs 7/11/12 left
uncovered) gets the same treatment before ROADMAP item 4 grows it:

  * ``wiring.build_wire`` — the 2-stage mpeek-driven lazy wire
    (ShmChannel.ensure_wired): no hang, no unsafe/mixed tier enable,
    degraded-all-off on mid-wire death, no post-revoke wire;
  * ``daemon.build_daemon`` — the multi-tenant warm-attach claim cycle
    (flock txn / epoch / truncate-reset / stale sweep / idle expiry),
    with the concurrent-claims admission variant (nsets instances under
    a quota — pre-verified in PR 13, shipped in PR 14), the bounded
    FIFO admission queue, and the exec-cache epoch discipline — the
    model grows in lockstep with runtime/daemon.py;
  * ``ft.build_ft`` — lease-detect → revoke flood (with re-flood) →
    shrink re-key: eventual PROC_FAILED delivery, no survivor parked
    forever on a dead or diverted peer, re-key never reuses a poisoned
    ctx/lane, reused regions never deliver torn words.

The nonblocking lane (coll/nbc/engine.py, PR 18's deposit/POLL/
complete device schedules) gets ``nbc.build_nbc``: the DAG scheduler —
dependency-ordered vertex issue, segment-wise async hardware dispatch,
wakeup-driven completion fan-out, the progress hook pumping parked
polls, persistent start re-init over exec-cache epoch reuse, and the
cancel/error unwind — proving deps-before-issue, deposit-before-poll,
issue-before-complete, drained-at-finalize, epoch freshness, and
deadlock freedom. Its ``TRACE_EVENTS`` table doubles as the runtime
event grammar of analysis/conform.py's NBC conformance automaton, so
the offline proof and the live-trace check share one source of truth.

The three-level hierarchy (PR 20) adds one model per new level:
``ici.build_mesh`` carries the multi-axis mesh phase composition
(RS-x -> RS-y -> AG-y -> AG-x over a px x py chip grid, with the
leaders-per-chip HBM fold in front) at contribution-set granularity —
its axis-phase-order invariant pins "no chip starts an axis's AG
before its own RS of that axis completed", the ordering bug class the
nested sub-shard decomposition makes load-bearing. ``flat2.build_net2``
models the np>64 node-leader bridge (coll/netcoll.py): group fold into
the node leader, seqlock-skeleton lane publish to the root leader's
bridge fold, fan-out of the total — with a node-leader-crash probe
proving an aborted wave poisons the cached split so the next
collective DEGRADES to sched instead of folding the dead lane.

Every model takes ``mutation=<name>`` seeding a realistic protocol
break (stamp-before-copy, missing final poll, throttle past the
deadline, ...); tests/test_modelcheck.py asserts the checker catches
each one and that the unmutated models are violation-free.
"""

from . import daemon, doorbell, flat2, ft, ici, lease, nbc, rma, seqlock, wiring  # noqa: F401,E501
from .explorer import Model, Result, Transition, Violation, explore  # noqa: F401


def mutation_matrix():
    """[(model label, builder kwargs -> Model, mutation name)] — every
    seeded protocol break the checker must catch. Builders are zero-arg
    callables returning the smallest model that exhibits the bug."""
    return [
        ("seqlock-allreduce", lambda: seqlock.build_allreduce(
            n=2, waves=1, mutation="stamp_before_copy"),
         "stamp_before_copy"),
        ("seqlock-allreduce", lambda: seqlock.build_allreduce(
            n=2, waves=1, mutation="no_reader_guard"),
         "no_reader_guard"),
        ("seqlock-allreduce", lambda: seqlock.build_allreduce(
            n=2, waves=2, mutation="no_overwrite_guard"),
         "no_overwrite_guard"),
        ("seqlock-allreduce", lambda: seqlock.build_allreduce(
            n=2, waves=1, crash=True, mutation="no_poison"),
         "no_poison"),
        ("seqlock-bcast", lambda: seqlock.build_bcast(
            n=3, mutation="no_arrival_wave"),
         "no_arrival_wave"),
        ("doorbell", lambda: doorbell.build(mutation="no_final_poll"),
         "no_final_poll"),
        ("doorbell", lambda: doorbell.build(mutation="ring_before_publish"),
         "ring_before_publish"),
        ("lease", lambda: lease.build(depart=True,
                                      mutation="departed_stale"),
         "departed_stale"),
        ("lease", lambda: lease.build(crash=True,
                                      mutation="throttle_too_long"),
         "throttle_too_long"),
        ("lease", lambda: lease.build(crash=True,
                                      mutation="inverted_compare"),
         "inverted_compare"),
        # hierarchical flat tier + multicast bcast (cp_flat2_*)
        ("flat2-hier", lambda: flat2.build_hier_allreduce(
            groups=2, k=2, mutation="xchg_no_guard"),
         "xchg_no_guard"),
        ("flat2-hier", lambda: flat2.build_hier_allreduce(
            groups=2, k=2, mutation="fanout_before_xchg"),
         "fanout_before_xchg"),
        ("flat2-hier", lambda: flat2.build_hier_allreduce(
            groups=2, k=2, crash=True, mutation="no_poison"),
         "no_poison"),
        ("flat2-mcast", lambda: flat2.build_mcast(
            n=3, waves=2, nbuf=1, mutation="publish_before_write"),
         "publish_before_write"),
        ("flat2-mcast", lambda: flat2.build_mcast(
            n=3, waves=2, nbuf=1, mutation="no_overwrite_guard"),
         "no_overwrite_guard"),
        ("flat2-mcast", lambda: flat2.build_mcast(
            n=3, waves=1, nbuf=1, mutation="no_first_sync"),
         "no_first_sync"),
        # three-level hierarchy (PR 20): multi-axis mesh phases with
        # the leaders-per-chip fold, and the net2 node-leader bridge
        ("ici-mesh", lambda: ici.build_mesh(
            px=2, py=2, mutation="ag_before_rs_crossaxis"),
         "ag_before_rs_crossaxis"),
        ("ici-mesh", lambda: ici.build_mesh(
            px=2, py=2, k=2, mutation="leader_fold_skipped"),
         "leader_fold_skipped"),
        ("flat2-net2", lambda: flat2.build_net2(
            groups=2, k=2, mutation="bridge_before_group_fold"),
         "bridge_before_group_fold"),
        ("flat2-net2", lambda: flat2.build_net2(
            groups=2, k=2, mutation="fanout_before_bridge"),
         "fanout_before_bridge"),
        ("flat2-net2", lambda: flat2.build_net2(
            groups=2, k=2, crash=True,
            mutation="leader_crash_no_poison"),
         "leader_crash_no_poison"),
        # chunk-credit remote-DMA ring (ops/pallas_ici.py)
        ("ici-ring", lambda: ici.build_ring(
            n=2, chunks=4, depth=2, mutation="no_credit_wait"),
         "no_credit_wait"),
        ("ici-ring", lambda: ici.build_ring(
            n=2, chunks=2, depth=2, mutation="slot_off_by_one"),
         "slot_off_by_one"),
        ("ici-ring", lambda: ici.build_ring(
            n=2, chunks=2, depth=2, mutation="depth_mismatch"),
         "depth_mismatch"),
        ("ici-ring", lambda: ici.build_ring(
            n=2, chunks=2, depth=2, mutation="signal_before_copy"),
         "signal_before_copy"),
        ("ici-ring", lambda: ici.build_ring(
            n=3, chunks=2, depth=2, bidir=True,
            mutation="bidir_shared_slot"),
         "bidir_shared_slot"),
        ("ici-ring", lambda: ici.build_ring(
            n=2, chunks=2, depth=2, mutation="recv_before_send_wave"),
         "recv_before_send_wave"),
        ("ici-ring", lambda: ici.build_ring(
            n=2, chunks=2, depth=2, mutation="scale_after_payload"),
         "scale_after_payload"),
        # MoE-shaped alltoallv wire (ops/pallas_alltoall.py): per-peer
        # variable chunk counts on the global-counter slot schedule
        ("ici-a2av", lambda: ici.build_alltoallv(
            n=2, depth=2, counts=[[0, 1], [3, 0]],
            mutation="skewed_count_slot"),
         "skewed_count_slot"),
        ("ici-a2av", lambda: ici.build_alltoallv(
            n=2, depth=2, counts=[[0, 0], [2, 0]],
            mutation="zero_count_credit_leak"),
         "zero_count_credit_leak"),
        ("ici-a2av", lambda: ici.build_alltoallv(
            n=2, depth=2, counts=[[0, 1], [3, 0]],
            mutation="local_width_wire"),
         "local_width_wire"),
        ("ici-a2av", lambda: ici.build_alltoallv(
            n=2, depth=2, counts=[[0, 0], [2, 0]],
            mutation="zero_count_entry_skip"),
         "zero_count_entry_skip"),
        # NBC DAG scheduler (coll/nbc/engine.py)
        ("nbc-dag", lambda: nbc.build_nbc(
            shape="device", segs=2, mutation="issue_ignores_deps"),
         "issue_ignores_deps"),
        ("nbc-dag", lambda: nbc.build_nbc(
            shape="device", segs=1, mutation="poll_never_pumped"),
         "poll_never_pumped"),
        ("nbc-dag", lambda: nbc.build_nbc(
            shape="net", mutation="lost_completion_wakeup"),
         "lost_completion_wakeup"),
        ("nbc-dag", lambda: nbc.build_nbc(
            shape="device", segs=2, error=True,
            mutation="unwind_leaves_inflight"),
         "unwind_leaves_inflight"),
        ("nbc-dag", lambda: nbc.build_nbc(
            shape="device", segs=1, persistent=True,
            mutation="stale_persistent_reuse"),
         "stale_persistent_reuse"),
        ("nbc-dag", lambda: nbc.build_nbc(
            shape="net", mutation="spurious_completion"),
         "spurious_completion"),
        # passive-target one-sided epoch (ops/pallas_rma.py)
        ("rma-passive", lambda: rma.build_passive(
            chunks=3, depth=2, cells=1, mutation="flush_skips_chunk"),
         "flush_skips_chunk"),
        ("rma-passive", lambda: rma.build_passive(
            chunks=3, depth=2, cells=1, mutation="unlock_before_drain"),
         "unlock_before_drain"),
        ("rma-passive", lambda: rma.build_passive(
            chunks=3, depth=2, cells=1, mutation="no_target_fold_order"),
         "no_target_fold_order"),
        ("rma-passive", lambda: rma.build_passive(
            chunks=3, depth=2, cells=1, mutation="torn_window_read"),
         "torn_window_read"),
        ("rma-passive", lambda: rma.build_passive(
            chunks=3, depth=2, cells=1, mutation="no_lock_wait"),
         "no_lock_wait"),
        # 2-stage lazy wire (ShmChannel.ensure_wired / try_wire)
        ("wiring", lambda: wiring.build_wire(
            2, caps=(1, 0), mutation="skip_unanimity"),
         "skip_unanimity"),
        ("wiring", lambda: wiring.build_wire(
            2, crash=True, mutation="no_dead_exclude"),
         "no_dead_exclude"),
        ("wiring", lambda: wiring.build_wire(
            2, crash=True, mutation="no_degrade"),
         "no_degrade"),
        ("wiring", lambda: wiring.build_wire(
            2, caps=(0, 1), mutation="verdict_before_cards"),
         "verdict_before_cards"),
        ("wiring", lambda: wiring.build_wire(
            3, crash=True, revoke=True, mutation="wire_after_revoke"),
         "wire_after_revoke"),
        # warm-attach daemon claim cycle (runtime/daemon.py)
        ("daemon-claim", lambda: daemon.build_daemon(
            2, crash=True, mutation="no_reset"),
         "no_reset"),
        ("daemon-claim", lambda: daemon.build_daemon(
            3, mutation="release_no_epoch"),
         "release_no_epoch"),
        ("daemon-claim", lambda: daemon.build_daemon(
            2, mutation="sweep_live_owner"),
         "sweep_live_owner"),
        ("daemon-claim", lambda: daemon.build_daemon(
            2, mutation="expiry_reaps_claimed"),
         "expiry_reaps_claimed"),
        ("daemon-claim", lambda: daemon.build_daemon(
            2, crash=True, mutation="sweep_never_fires"),
         "sweep_never_fires"),
        ("daemon-claim", lambda: daemon.build_daemon(
            2, concurrent=True, nsets=2, quota=1,
            mutation="over_quota"),
         "over_quota"),
        # the PR 14 multi-tenant surface: bounded FIFO admission queue,
        # concurrency-safe idle expiry, exec-cache epoch discipline
        ("daemon-claim", lambda: daemon.build_daemon(
            2, concurrent=True, nsets=2, quota=1,
            mutation="queue_skips_admission"),
         "queue_skips_admission"),
        ("daemon-claim", lambda: daemon.build_daemon(
            2, mutation="queue_drops_waiter"),
         "queue_drops_waiter"),
        ("daemon-claim", lambda: daemon.build_daemon(
            2, concurrent=True, nsets=2, quota=2,
            mutation="expiry_checks_set0"),
         "expiry_checks_set0"),
        ("daemon-claim", lambda: daemon.build_daemon(
            2, cache=True, mutation="cache_stale_serve"),
         "cache_stale_serve"),
        # ULFM lease-detect / revoke / shrink propagation (ft/ulfm.py)
        ("ft-ulfm", lambda: ft.build_ft(
            3, mutation="no_revoke_unwind"),
         "no_revoke_unwind"),
        ("ft-ulfm", lambda: ft.build_ft(
            3, partial_flood=True, mutation="no_reflood"),
         "no_reflood"),
        ("ft-ulfm", lambda: ft.build_ft(
            3, mutation="detect_disabled"),
         "detect_disabled"),
        ("ft-ulfm", lambda: ft.build_ft(
            3, reuse=True, mutation="no_poison"),
         "no_poison"),
        ("ft-ulfm", lambda: ft.build_ft(
            3, mutation="rekey_same_ctx"),
         "rekey_same_ctx"),
    ]
