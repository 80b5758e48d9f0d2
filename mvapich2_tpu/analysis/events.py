"""Pass ``events`` — the trace event-coverage doctor.

The conformance checker (analysis/conform.py) replays a run's traces
through per-protocol automata; an emitted event outside every
automaton's grammar is only caught at *run* time (the driver's
grammar-coverage violation) and only if some job happens to exercise
the site. This pass closes the gap statically — the ``lat_dev_nbc``
silent-drop bug class (PR 18: a recorded name nobody's table knew,
found by hand) becomes a mechanically caught lint finding:

  * every ``tracer.record(layer, name, ...)`` site in the package must
    emit a (layer, name) the conformance grammar covers — f-string
    names become prefix patterns (``f"rma_{kind}"`` -> ``rma_*``), and
    a name passed through a wrapper parameter is resolved one level
    through the wrapper's call sites (the ``_trace_rma`` idiom), a
    local bound once to a literal or an f-string reads as that;
  * every ``_NT_EVENTS`` member (trace/native.py's NTE->region map —
    the python mirror the native pass already proves dense against the
    C enum) must carry a protocol region AND be covered by the
    cplane grammar, so a new NTE_* can't land without a conformance
    automaton learning it;
  * every ``rec_us``/``rec_since`` latency sample must name a
    ``_MET_HISTS`` histogram block — an unknown name is accepted and
    silently dropped by the writer, which is exactly the bug class.

The native.py-dependent checks skip quietly when trace/native.py is
not among the scanned modules (fixture runs)."""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from .conform import grammar_covers
from .core import Finding, LintPass, SourceModule


def _name_pattern(node: ast.AST) -> Optional[str]:
    """A record-name argument as a literal or prefix pattern; None =
    not resolvable from this expression alone."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        pat = "".join(str(part.value) if isinstance(part, ast.Constant)
                      else "*" for part in node.values)
        stars = pat.count("*")
        if stars == 0:
            return pat
        # one interpolation at either edge keeps its anchor (the
        # f"rma_{kind}" / f"{self.name}_send" idioms); anything
        # messier degrades to the full wildcard
        if stars == 1 and (pat.startswith("*") or pat.endswith("*")):
            return pat
        return "*"
    return None


def _arg(call: ast.Call, idx: int, kw: str) -> Optional[ast.AST]:
    if len(call.args) > idx:
        return call.args[idx]
    for k in call.keywords:
        if k.arg == kw:
            return k.value
    return None


class EventCoveragePass(LintPass):
    id = "events"
    doc = ("tracer.record()/NTE/rec_us event names must be covered by "
           "a conformance automaton grammar (analysis/conform.py) and "
           "the metrics histogram table")

    # ------------------------------------------------------------------
    def run(self, modules: List[SourceModule]) -> List[Finding]:
        out: List[Finding] = []
        callindex = self._call_index(modules)
        native_mod = next((m for m in modules
                           if m.relpath.replace("\\", "/")
                           .endswith("trace/native.py")), None)
        hists = self._literal_tuple(native_mod, "_MET_HISTS") \
            if native_mod else None

        for mod in modules:
            parents = self._parents(mod.tree)
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                if not isinstance(fn, ast.Attribute):
                    continue
                if fn.attr == "record":
                    out.extend(self._check_record(
                        mod, node, parents, callindex))
                elif fn.attr in ("rec_us", "rec_since") and \
                        hists is not None:
                    out.extend(self._check_hist(mod, node, hists))

        if native_mod is not None:
            out.extend(self._check_nt_events(native_mod))
        return [f for f in out if f is not None]

    # ------------------------------------------------------------------
    def _check_record(self, mod: SourceModule, call: ast.Call,
                      parents: Dict[ast.AST, ast.AST],
                      callindex: Dict[str, List[ast.Call]]
                      ) -> List[Optional[Finding]]:
        layer_node = _arg(call, 0, "layer")
        name_node = _arg(call, 1, "name")
        if layer_node is None or name_node is None:
            return []          # not the tracer signature
        layer = _name_pattern(layer_node)
        if layer is None or "*" in layer:
            return []          # dynamic layer: some other API's .record
        names = self._resolve(name_node, call, parents, callindex)
        out = []
        for name in names:
            if not grammar_covers(layer, name):
                out.append(self.finding(
                    mod, call.lineno,
                    f"tracer event [{layer}] {name} is outside every "
                    "conformance automaton's grammar — mv2tconform "
                    "would report it as grammar-coverage drift; teach "
                    "an automaton in analysis/conform.py (or "
                    "model/nbc.TRACE_EVENTS) this name"))
        return out

    def _resolve(self, node: ast.AST, call: ast.Call,
                 parents: Dict[ast.AST, ast.AST],
                 callindex: Dict[str, List[ast.Call]]) -> List[str]:
        """Record-name expression -> emitted name patterns. A bare
        parameter resolves one level through the enclosing function's
        call sites; anything deeper degrades to "*" (covered only by a
        wildcard-grammar layer, e.g. the mpi interposition lane)."""
        pat = _name_pattern(node)
        if pat is not None:
            return [pat]
        if isinstance(node, ast.Name):
            fdef = self._enclosing_def(call, parents)
            # a local bound once in the function, to a literal or an
            # f-string (coll/device.py:_run's ``span``), reads as that
            bound = [] if fdef is None else [
                n.value for n in ast.walk(fdef)
                if isinstance(n, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == node.id
                    for t in n.targets)]
            pat = _name_pattern(bound[0]) if len(bound) == 1 else None
            if pat is not None:
                return [pat]
            if fdef is not None and node.id in \
                    [a.arg for a in fdef.args.args]:
                idx = [a.arg for a in fdef.args.args].index(node.id)
                # drop self for method call sites
                meth = bool(fdef.args.args) and \
                    fdef.args.args[0].arg in ("self", "cls")
                pos = idx - (1 if meth else 0)
                pats = []
                for site in callindex.get(fdef.name, ()):
                    a = _arg(site, pos, node.id)
                    p = _name_pattern(a) if a is not None else None
                    pats.append(p if p is not None else "*")
                if pats:
                    return sorted(set(pats))
        return ["*"]

    # ------------------------------------------------------------------
    def _check_hist(self, mod: SourceModule, call: ast.Call,
                    hists: Tuple[str, ...]) -> List[Optional[Finding]]:
        if not call.args:
            return []
        pat = _name_pattern(call.args[0])
        if pat is None:
            return []
        if pat.endswith("*"):
            ok = any(h.startswith(pat[:-1]) for h in hists)
        else:
            ok = pat in hists
        if ok:
            return []
        return [self.finding(
            mod, call.lineno,
            f"latency sample {pat!r} names no _MET_HISTS histogram "
            "block (trace/native.py) — the writer accepts unknown "
            "names and silently drops the sample (the lat_dev_nbc "
            "bug class)")]

    # ------------------------------------------------------------------
    def _check_nt_events(self, mod: SourceModule
                         ) -> List[Optional[Finding]]:
        out = []
        for assign in ast.walk(mod.tree):
            if not isinstance(assign, ast.Assign):
                continue
            if not any(isinstance(t, ast.Name) and t.id == "_NT_EVENTS"
                       for t in assign.targets):
                continue
            if not isinstance(assign.value, (ast.List, ast.Tuple)):
                continue
            for elt in assign.value.elts:
                try:
                    name, region = ast.literal_eval(elt)
                except (ValueError, SyntaxError):
                    out.append(self.finding(
                        mod, elt.lineno,
                        "_NT_EVENTS entry is not a literal "
                        "(name, region) pair"))
                    continue
                if not region:
                    out.append(self.finding(
                        mod, elt.lineno,
                        f"NTE event {name!r} has no protocol region "
                        "in the NTE->region map"))
                if not grammar_covers("cplane", name):
                    out.append(self.finding(
                        mod, elt.lineno,
                        f"NTE event {name!r} is outside every "
                        "conformance automaton's cplane grammar — a "
                        "native emit nobody can verify; teach an "
                        "automaton in analysis/conform.py this name"))
        return out

    # ------------------------------------------------------------------
    @staticmethod
    def _call_index(modules: List[SourceModule]
                    ) -> Dict[str, List[ast.Call]]:
        """function-name -> every call site in the package (for the
        one-level wrapper-parameter resolution)."""
        index: Dict[str, List[ast.Call]] = {}
        for mod in modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                fname = fn.id if isinstance(fn, ast.Name) else (
                    fn.attr if isinstance(fn, ast.Attribute) else None)
                if fname:
                    index.setdefault(fname, []).append(node)
        return index

    @staticmethod
    def _parents(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
        return {child: parent for parent in ast.walk(tree)
                for child in ast.iter_child_nodes(parent)}

    @staticmethod
    def _enclosing_def(node: ast.AST, parents: Dict[ast.AST, ast.AST]
                       ) -> Optional[ast.FunctionDef]:
        cur = parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = parents.get(cur)
        return None

    @staticmethod
    def _literal_tuple(mod: SourceModule, name: str
                       ) -> Optional[Tuple[str, ...]]:
        for assign in ast.walk(mod.tree):
            if isinstance(assign, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == name
                    for t in assign.targets):
                try:
                    val = ast.literal_eval(assign.value)
                except (ValueError, SyntaxError):
                    return None
                return tuple(str(v) for v in val)
        return None
