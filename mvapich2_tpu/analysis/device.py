"""Pass ``device`` — DMA/semaphore discipline of the Pallas device lane.

The host shm protocol earned its verification net in PR 7 (the
``native`` pass + the model checker); this pass is the device half. The
kernel modules (ops/pallas_ici.py, ops/pallas_ring.py, rma/device.py)
drive raw Mosaic DMA: every ``make_async_copy``/``make_async_remote_copy``
is a contract with the hardware — an unawaited handle is a use-after-free
of a VMEM slot, an unpaired credit semaphore is the 64 MiB deadlock.
Five invariant families, all syntactic:

  * **copy/wait pairing** — a handle bound from ``make_async_*copy`` and
    ``.start()``ed must reach a matching wait on every control-flow path
    out of the function (``.wait()``, or ``.wait_send()``+``.wait_recv()``
    for remote copies), or be *parked* into a pending container whose
    drain is checked module-wide. An early ``return`` past a started,
    unwaited handle is a finding — the classic kernel-exit race.
  * **park/drain** — every container that receives parked handles must
    have drain sites (wait on a popped / subscripted / iterated value);
    containers of remote handles must drain BOTH semaphores
    (``wait_send`` and ``wait_recv``, or a full ``wait``). A
    ``pending_*`` map that is never filled nor drained is dead
    device-protocol state (it lies to the watchdog's lane map).
  * **semaphore pairing** — per module, the set of credit semaphores
    that are ``semaphore_signal``ed must equal the set that is
    ``semaphore_wait``ed (a signal-only sem leaks credits; a wait-only
    sem is a guaranteed hang).
  * **credit gates** — where a credit-semaphore op sits behind a
    creditless gate (an ``if`` on a ``credits``-ish flag or a
    ``sem is None`` check), the gate (or its def) must be annotated
    ``# device: hw-only``. The mark names the code a ``credits=False``
    run skips: the credit handshake, which runs on hardware and — since
    the TPU interpreter executes remote signals — in every CPU test, and
    is off only in a schedule experiment that asks for it. (The name is
    from the days when no interpreter could run it.) Ungated credit ops
    are fine: they always run.
  * **VMEM budget** — scratch ``pltpu.VMEM((ndir, depth, chunk, 128),
    ...)``
    allocations are evaluated against every committed configuration
    (the ICI_CHUNK_BYTES / ICI_PIPELINE_DEPTH cvar defaults parsed from
    mpit.py, plus each committed tuning profile's ici_chunk_bytes):
    a chunk-size/depth combination that cannot fit is a lint failure
    here, not a Mosaic OOM on the TPU host.

Annotation grammar (ordinary comments, same line as the code):

    def _grant(self, d):            # device: hw-only
    rdma.start()                    # device: escapes  (handle outlives
                                    # the static scan — last resort)
    x.start()                       # mv2tlint: ignore[device]

``device_lane_map()`` exports the harvested park/drain/semaphore map for
the stall watchdog and ``mpistat --device-map`` — the device analog of
the native pass's ``shared_field_map``.
"""

from __future__ import annotations

import ast
import json
import os
from typing import Dict, List, Optional, Set, Tuple

from .core import (Finding, LintPass, PKG_ROOT, SourceModule, const_int,
                   parent_map, scan_paths, terminal_name)

_COPY_CTORS = {"make_async_copy": False, "make_async_remote_copy": True}
_WAITS = {"wait", "wait_send", "wait_recv"}
_SEM_OPS = {"semaphore_signal", "semaphore_wait"}

# The scratch-budget ceiling: ~16 MiB of VMEM per core, minus headroom
# for the kernel's own working set (the reduce reads one recv chunk and
# one acc chunk beyond the slot arrays). Itemsize is evaluated at 4
# bytes — the widest dtype the kernels accept with x64 off.
DEVICE_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
_BUDGET_ITEMSIZE = 4

PROFILE_DIR = os.path.join(PKG_ROOT, "profiles")


def _call_name(node: ast.Call) -> Optional[str]:
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def _sem_operand_name(node: ast.AST) -> Optional[str]:
    """Terminal semaphore name of a ``sem`` / ``sem.at[i]`` operand."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and node.attr == "at":
        node = node.value
    return terminal_name(node)


def _credit_gate_test(test: ast.AST) -> bool:
    """True when an ``if`` test reads as a creditless gate: any name
    containing 'credit', or an ``is (not) None`` probe of a *sem name."""
    for sub in ast.walk(test):
        if isinstance(sub, (ast.Name, ast.Attribute)):
            t = terminal_name(sub)
            if t and "credit" in t.lower():
                return True
        if isinstance(sub, ast.Compare) \
                and any(isinstance(op, (ast.Is, ast.IsNot))
                        for op in sub.ops):
            t = terminal_name(sub.left)
            if t and ("sem" in t.lower() or "credit" in t.lower()):
                return True
    return False


def _is_device_module(mod: SourceModule) -> bool:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            name = _call_name(node)
            if name in _COPY_CTORS or name in _SEM_OPS or name == "VMEM":
                return True
    return False


# ---------------------------------------------------------------------------
# copy/wait flow analysis (per function)
# ---------------------------------------------------------------------------

class _HState:
    """One tracked handle inside one function."""

    __slots__ = ("line", "remote", "started", "discharged", "waits")

    def __init__(self, line: int, remote: bool):
        self.line = line
        self.remote = remote
        self.started = False
        self.discharged = False
        self.waits: Set[str] = set()

    def copy(self) -> "_HState":
        h = _HState(self.line, self.remote)
        h.started, h.discharged = self.started, self.discharged
        h.waits = set(self.waits)
        return h

    def note_wait(self, kind: str) -> None:
        self.waits.add(kind)
        if "wait" in self.waits:
            self.discharged = True
        elif self.remote and {"wait_send", "wait_recv"} <= self.waits:
            self.discharged = True


def _copy_live(live: Dict[str, _HState]) -> Dict[str, _HState]:
    return {k: v.copy() for k, v in live.items()}


def _merge(a: Dict[str, _HState], b: Dict[str, _HState]) -> Dict[str, _HState]:
    out: Dict[str, _HState] = {}
    for name in set(a) | set(b):
        ha, hb = a.get(name), b.get(name)
        if ha is None or hb is None:
            out[name] = (ha or hb).copy()
            continue
        h = ha.copy()
        h.started = ha.started or hb.started
        h.discharged = ha.discharged and hb.discharged
        h.waits = ha.waits & hb.waits
        out[name] = h
    return out


class DevicePass(LintPass):
    id = "device"
    doc = ("Pallas DMA discipline: copy handles waited on every path, "
           "pending maps drained, credit semaphores paired + hw-only "
           "gated, VMEM scratch budget fits every committed config")

    def __init__(self, profiles: Optional[List[str]] = None):
        # profiles: tuning-profile JSONs whose ici_chunk_bytes feed the
        # budget estimator; None = the committed profiles/ directory
        self.profiles = profiles

    # ------------------------------------------------------------------
    def run(self, modules: List[SourceModule]) -> List[Finding]:
        out: List[Finding] = []
        dev_mods = [m for m in modules if _is_device_module(m)]
        configs = self._budget_configs(modules)
        drains_of: Dict[str, Dict[str, Set[str]]] = {}
        for mod in dev_mods:
            self._harvest_drains(mod, drains_of.setdefault(mod.path, {}))
        for mod in dev_mods:
            parks: Dict[str, dict] = {}
            drains = drains_of[mod.path]
            self._check_unbound(mod, out)
            self._harvest_parks_and_flow(mod, parks, out)
            self._check_containers(
                mod, parks, self._with_imported(mod, drains_of), out)
            self._check_dead_pending(mod, parks, drains, out)
            self._check_semaphores(mod, out)
            self._check_vmem_budget(mod, configs, out)
        return out

    # -- unbound constructor calls -------------------------------------
    def _check_unbound(self, mod: SourceModule, out: List[Finding]) -> None:
        parents = parent_map(mod.tree)
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and _call_name(node) in _COPY_CTORS):
                continue
            par = parents.get(node)
            bound = isinstance(par, (ast.Assign, ast.AnnAssign)) \
                and getattr(par, "value", None) is node
            if bound:
                continue
            if isinstance(par, ast.Return):
                continue        # handed to the caller — their contract
            f = self.finding(mod, node.lineno,
                             f"async copy '{_call_name(node)}' is never "
                             "bound to a handle — its wait is "
                             "unreachable")
            if f is not None:
                out.append(f)

    # -- flow analysis + park harvesting -------------------------------
    def _harvest_parks_and_flow(self, mod: SourceModule,
                                parks: Dict[str, dict],
                                out: List[Finding]) -> None:
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._flow_fn(mod, node, parks, out)

    def _flow_fn(self, mod: SourceModule, fn, parks: Dict[str, dict],
                 out: List[Finding]) -> None:
        reported: Set[str] = set()

        def report(name: str, h: _HState, line: int) -> None:
            if name in reported:
                return
            reported.add(name)
            kind = "remote copy" if h.remote else "copy"
            f = self.finding(mod, line,
                             f"async {kind} '{name}' (started in "
                             f"'{fn.name}') can exit without a "
                             "matching wait on this path")
            if f is not None:
                out.append(f)

        def park(container: str, remote: bool, line: int) -> None:
            info = parks.setdefault(container, {"remote": False,
                                                "lines": []})
            info["remote"] = info["remote"] or remote
            info["lines"].append(line)

        def handle_call(call: ast.Call, live: Dict[str, _HState]) -> None:
            fnode = call.func
            if not isinstance(fnode, ast.Attribute):
                return
            recv = fnode.value
            name = recv.id if isinstance(recv, ast.Name) else None
            if name is None or name not in live:
                return
            h = live[name]
            if fnode.attr == "start":
                h.started = True
            elif fnode.attr in _WAITS:
                h.note_wait(fnode.attr)

        def stmt(st, live: Dict[str, _HState]) -> Tuple[Dict, bool]:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                return live, False      # separate contract
            if isinstance(st, (ast.Assign, ast.AnnAssign)):
                value = st.value
                targets = st.targets if isinstance(st, ast.Assign) \
                    else ([st.target] if st.value is not None else [])
                if isinstance(value, ast.Call) \
                        and _call_name(value) in _COPY_CTORS:
                    remote = _COPY_CTORS[_call_name(value)]
                    for t in targets:
                        if isinstance(t, ast.Name):
                            if not mod.suppressed(st.lineno, self.id) \
                                    and mod.annotation(st.lineno,
                                                       "device") \
                                    != "escapes":
                                live[t.id] = _HState(st.lineno, remote)
                        elif isinstance(t, (ast.Subscript, ast.Attribute)):
                            c = terminal_name(t.value) if isinstance(
                                t, ast.Subscript) else t.attr
                            if c:
                                park(c, remote, st.lineno)
                    return live, False
                if isinstance(value, ast.Name) and value.id in live:
                    for t in targets:
                        if isinstance(t, (ast.Subscript, ast.Attribute)):
                            c = terminal_name(t.value) if isinstance(
                                t, ast.Subscript) else t.attr
                            if c:
                                park(c, live[value.id].remote, st.lineno)
                                live[value.id].discharged = True
                return live, False
            if isinstance(st, (ast.Return, ast.Raise)):
                for name, h in live.items():
                    if h.started and not h.discharged:
                        report(name, h, st.lineno)
                return live, True
            if isinstance(st, ast.If):
                lt, et = seq(st.body, _copy_live(live))
                lf, ef = seq(st.orelse, _copy_live(live))
                if et and ef:
                    return live, True
                if et:
                    return lf, False
                if ef:
                    return lt, False
                return _merge(lt, lf), False
            if isinstance(st, (ast.For, ast.AsyncFor, ast.While)):
                lb, _eb = seq(st.body, _copy_live(live))
                live = _merge(live, lb)
                if st.orelse:
                    live, ex = seq(st.orelse, live)
                    return live, ex
                return live, False
            if isinstance(st, (ast.With, ast.AsyncWith)):
                return seq(st.body, live)
            if isinstance(st, ast.Try):
                lb, eb = seq(st.body, _copy_live(live))
                merged = lb if not eb else _copy_live(live)
                for handler in st.handlers:
                    lh, eh = seq(handler.body, _copy_live(live))
                    if not eh:
                        merged = _merge(merged, lh)
                if st.orelse:
                    merged, _ = seq(st.orelse, merged)
                if st.finalbody:
                    merged, ex = seq(st.finalbody, merged)
                    return merged, ex
                return merged, False
            # expression statements and everything else: scan calls
            for sub in ast.walk(st):
                if isinstance(sub, ast.Call):
                    handle_call(sub, live)
            return live, False

        def seq(stmts, live: Dict[str, _HState]) -> Tuple[Dict, bool]:
            exited = False
            for st in stmts:
                live, exited = stmt(st, live)
                if exited:
                    break
            return live, exited

        live, exited = seq(fn.body, {})
        if not exited:
            last = fn.body[-1]
            line = getattr(last, "end_lineno", None) or last.lineno
            for name, h in live.items():
                if h.started and not h.discharged:
                    report(name, h, line)

    # -- drains ---------------------------------------------------------
    def _harvest_drains(self, mod: SourceModule,
                        drains: Dict[str, Set[str]]) -> None:
        # name -> container, for `h = X.pop(...)` and `for k, h in
        # X.items()` bindings (possibly wrapped in list()/tuple()/sorted())
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            bound: Dict[str, str] = {}
            for sub in ast.walk(node):
                if isinstance(sub, ast.Assign) \
                        and isinstance(sub.value, ast.Call):
                    c = self._pop_container(sub.value)
                    if c:
                        for t in sub.targets:
                            if isinstance(t, ast.Name):
                                bound[t.id] = c
                if isinstance(sub, (ast.For, ast.AsyncFor)):
                    c = self._iter_container(sub.iter)
                    if c is None:
                        continue
                    targets = sub.target.elts if isinstance(
                        sub.target, ast.Tuple) else [sub.target]
                    for t in targets:
                        if isinstance(t, ast.Name):
                            bound[t.id] = c
            for sub in ast.walk(node):
                if not (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in _WAITS):
                    continue
                recv = sub.func.value
                c = None
                if isinstance(recv, ast.Subscript):
                    c = terminal_name(recv.value)
                elif isinstance(recv, ast.Call):
                    c = self._pop_container(recv)
                elif isinstance(recv, ast.Name):
                    c = bound.get(recv.id)
                elif isinstance(recv, ast.Attribute):
                    c = recv.attr
                if c:
                    drains.setdefault(c, set()).add(sub.func.attr)

    @staticmethod
    def _pop_container(call: ast.Call) -> Optional[str]:
        fn = call.func
        if isinstance(fn, ast.Attribute) and fn.attr == "pop":
            return terminal_name(fn.value)
        return None

    @staticmethod
    def _iter_container(it: ast.AST) -> Optional[str]:
        if isinstance(it, ast.Call) and isinstance(it.func, ast.Name) \
                and it.func.id in ("list", "tuple", "sorted") and it.args:
            it = it.args[0]
        if isinstance(it, ast.Call) and isinstance(it.func, ast.Attribute) \
                and it.func.attr in ("items", "values"):
            return terminal_name(it.func.value)
        return None

    @staticmethod
    def _with_imported(mod: SourceModule,
                       drains_of: Dict[str, Dict[str, Set[str]]]
                       ) -> Dict[str, Set[str]]:
        """``mod``'s drains and those of the scanned device modules
        beside it that it imports names from: a streamer subclassed
        from a sibling module (ops/pallas_alltoall's, from
        ops/pallas_ici) parks into maps its base class drains."""
        merged = {c: set(k) for c, k in drains_of[mod.path].items()}
        here = os.path.dirname(mod.path)
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.ImportFrom) and node.module):
                continue
            sibling = os.path.join(
                here, node.module.rsplit(".", 1)[-1] + ".py")
            for c, kinds in drains_of.get(sibling, {}).items():
                merged.setdefault(c, set()).update(kinds)
        return merged

    # -- container adequacy ---------------------------------------------
    def _check_containers(self, mod: SourceModule, parks: Dict[str, dict],
                          drains: Dict[str, Set[str]],
                          out: List[Finding]) -> None:
        for name, info in sorted(parks.items()):
            line = info["lines"][0]
            kinds = drains.get(name, set())
            if not kinds:
                f = self.finding(mod, line,
                                 f"handles parked into '{name}' are "
                                 "never drained (no wait on a popped/"
                                 "subscripted/iterated value)")
                if f is not None:
                    out.append(f)
                continue
            if info["remote"] and "wait" not in kinds \
                    and not {"wait_send", "wait_recv"} <= kinds:
                missing = sorted({"wait_send", "wait_recv"} - kinds)
                f = self.finding(mod, line,
                                 f"remote handles parked into '{name}' "
                                 f"drain only {sorted(kinds)} — missing "
                                 f"{missing} (both DMA semaphores must "
                                 "be consumed)")
                if f is not None:
                    out.append(f)

    def _check_dead_pending(self, mod: SourceModule, parks: Dict[str, dict],
                            drains: Dict[str, Set[str]],
                            out: List[Finding]) -> None:
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            value = node.value
            if not (isinstance(value, ast.Dict) and not value.keys):
                continue
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                name = t.attr if isinstance(t, ast.Attribute) else \
                    (t.id if isinstance(t, ast.Name) else None)
                if name is None or not name.startswith("pending"):
                    continue
                if name in parks or name in drains:
                    continue
                f = self.finding(mod, node.lineno,
                                 f"pending-handle map '{name}' is never "
                                 "filled or drained — dead device-"
                                 "protocol state (it lies to the "
                                 "watchdog lane map)")
                if f is not None:
                    out.append(f)

    # -- credit semaphores ----------------------------------------------
    def _sem_sites(self, mod: SourceModule):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and _call_name(node) in _SEM_OPS \
                    and node.args:
                sem = _sem_operand_name(node.args[0])
                if sem:
                    yield node, _call_name(node), sem

    def _check_semaphores(self, mod: SourceModule,
                          out: List[Finding]) -> None:
        signals: Dict[str, int] = {}
        waits: Dict[str, int] = {}
        for node, op, sem in self._sem_sites(mod):
            (signals if op == "semaphore_signal" else waits).setdefault(
                sem, node.lineno)
        for sem, line in sorted(signals.items()):
            if sem not in waits:
                f = self.finding(mod, line,
                                 f"semaphore '{sem}' is signaled but "
                                 "never waited in this module — leaked "
                                 "credits")
                if f is not None:
                    out.append(f)
        for sem, line in sorted(waits.items()):
            if sem not in signals:
                f = self.finding(mod, line,
                                 f"semaphore '{sem}' is waited but "
                                 "never signaled in this module — a "
                                 "guaranteed hang")
                if f is not None:
                    out.append(f)
        # every creditless gate around a credit op is annotated
        parents = parent_map(mod.tree)
        seen_gates: Set[Tuple[int, str]] = set()
        for node, op, sem in self._sem_sites(mod):
            gate_line = self._gate_line(node, parents)
            if gate_line is None:
                continue        # ungated: runs wherever the kernel runs
            if (gate_line, sem) in seen_gates:
                continue
            seen_gates.add((gate_line, sem))
            fn = self._enclosing_fn(node, parents)
            annotated = mod.annotation(gate_line, "device") == "hw-only" \
                or (fn is not None
                    and mod.annotation(fn.lineno, "device") == "hw-only")
            if not annotated:
                f = self.finding(mod, gate_line,
                                 f"creditless gate for '{sem}' is not "
                                 "annotated '# device: hw-only' — "
                                 "credit-gated code must be marked")
                if f is not None:
                    out.append(f)

    @staticmethod
    def _enclosing_fn(node: ast.AST, parents):
        while node is not None:
            node = parents.get(node)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return node
        return None

    def _gate_line(self, node: ast.AST, parents) -> Optional[int]:
        """Line of the creditless gate covering ``node``: an enclosing
        ``if`` with a credit-ish test, or an earlier top-level
        early-return gate in the same function."""
        cur = node
        fn = None
        while cur is not None:
            par = parents.get(cur)
            if isinstance(par, ast.If) and _credit_gate_test(par.test):
                return par.lineno
            if isinstance(par, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = par
                break
            cur = par
        if fn is None:
            return None
        for st in fn.body:
            if st.lineno >= node.lineno:
                break
            if isinstance(st, ast.If) and _credit_gate_test(st.test) \
                    and st.body and isinstance(st.body[-1], ast.Return):
                return st.lineno
        return None

    # -- VMEM budget -----------------------------------------------------
    def _budget_configs(self, modules: List[SourceModule]):
        """[(label, chunk_bytes, depth)] from the cvar defaults in
        mpit.py and every committed profile's ici_chunk_bytes."""
        chunk_default, depth_default = 256 * 1024, 2
        for mod in modules:
            if not mod.relpath.endswith("mvapich2_tpu/mpit.py"):
                continue
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Call) and _call_name(node) == "cvar" \
                        and len(node.args) >= 2 \
                        and isinstance(node.args[0], ast.Constant):
                    v = const_int(node.args[1])
                    if node.args[0].value == "ICI_CHUNK_BYTES" \
                            and v is not None:
                        chunk_default = v
                    elif node.args[0].value == "ICI_PIPELINE_DEPTH" \
                            and v is not None:
                        depth_default = v
        configs = [("cvar defaults (mpit.py)", chunk_default,
                    depth_default)]
        paths = self.profiles
        if paths is None:
            try:
                paths = sorted(
                    os.path.join(PROFILE_DIR, f)
                    for f in os.listdir(PROFILE_DIR) if f.endswith(".json"))
            except OSError:
                paths = []
        for p in paths:
            try:
                with open(p) as fh:
                    doc = json.load(fh)
            except (OSError, ValueError):
                continue          # the profile doctor reports malformed files
            if doc.get("format") != "mv2t-tuning-profile-v1":
                continue
            kp = doc.get("profile", {}).get("kernel_params", {})
            cb = kp.get("ici_chunk_bytes")
            if isinstance(cb, int) and cb > 0:
                configs.append((os.path.basename(p), cb, depth_default))
        return configs

    def _check_vmem_budget(self, mod: SourceModule, configs,
                           out: List[Finding]) -> None:
        bufs = []           # (line, [dim names/ints])
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and _call_name(node) == "VMEM" and node.args):
                continue
            shape = node.args[0]
            if not isinstance(shape, ast.Tuple):
                continue
            dims = []
            for el in shape.elts:
                if isinstance(el, ast.Name):
                    dims.append(el.id)
                else:
                    v = const_int(el)
                    dims.append(v if v is not None else 1)
            bufs.append((node.lineno, dims))
        if not bufs:
            return
        for label, chunk_bytes, depth in configs:
            total = 0
            for _line, dims in bufs:
                size = _BUDGET_ITEMSIZE
                # a trailing lanes dim means 'chunk' counts rows of it
                lanes = 128 if any(isinstance(d, str) and "LANES" in d
                                   for d in dims) else 1
                for d in dims:
                    if isinstance(d, int):
                        size *= d
                    elif "LANES" in d:
                        size *= lanes
                    elif "chunk" in d:
                        size *= max(1, chunk_bytes // _BUDGET_ITEMSIZE
                                    // lanes)
                    elif "depth" in d:
                        size *= depth
                    elif "ndir" in d or "dir" in d:
                        size *= 2
                    # unknown symbolic dims count as 1 — the estimator
                    # under-approximates rather than cry wolf
                total += size
            if total > DEVICE_VMEM_BUDGET_BYTES:
                f = self.finding(
                    mod, bufs[0][0],
                    f"VMEM scratch budget {total} bytes under config "
                    f"'{label}' (chunk={chunk_bytes}, depth={depth}) "
                    f"exceeds the {DEVICE_VMEM_BUDGET_BYTES}-byte tier "
                    "cap — this combination cannot compile")
                if f is not None:
                    out.append(f)
                break      # one finding per module: name the first
                           # offending config, not every config


# ---------------------------------------------------------------------------
# the exported lane map (watchdog / mpistat parity with shared_field_map)
# ---------------------------------------------------------------------------

_DEVICE_DIRS = ("ops", "rma")
_lane_map_cache: Optional[Dict[str, dict]] = None


def device_lane_map(refresh: bool = False) -> Dict[str, dict]:
    """{name: info} for every pending-handle container and credit
    semaphore of the committed device modules, harvested by the same
    AST walk the lint pass runs — the device analog of the native
    pass's ``shared_field_map``. Keys:

      containers: kind='pending-map', remote, drains=[wait kinds], module
      semaphores: kind='credit-sem', signals/waits (site counts), module
    """
    global _lane_map_cache
    if _lane_map_cache is not None and not refresh:
        return _lane_map_cache
    out: Dict[str, dict] = {}
    p = DevicePass(profiles=[])
    for d in _DEVICE_DIRS:
        root = os.path.join(PKG_ROOT, d)
        if not os.path.isdir(root):
            continue
        modules, _errs = scan_paths([root])
        for mod in modules:
            if not _is_device_module(mod):
                continue
            parks: Dict[str, dict] = {}
            drains: Dict[str, Set[str]] = {}
            p._harvest_parks_and_flow(mod, parks, [])
            p._harvest_drains(mod, drains)
            for name, info in parks.items():
                out[name] = {"kind": "pending-map",
                             "remote": info["remote"],
                             "drains": sorted(drains.get(name, ())),
                             "module": mod.relpath}
            sig: Dict[str, int] = {}
            wai: Dict[str, int] = {}
            for _node, op, sem in p._sem_sites(mod):
                tgt = sig if op == "semaphore_signal" else wai
                tgt[sem] = tgt.get(sem, 0) + 1
            for sem in set(sig) | set(wai):
                key = sem if sem not in out else f"{sem}@{mod.relpath}"
                out[key] = {"kind": "credit-sem",
                            "signals": sig.get(sem, 0),
                            "waits": wai.get(sem, 0),
                            "module": mod.relpath}
    _lane_map_cache = out
    return out
