"""Pass ``pvars`` — pvar/cvar registry consistency.

The MPI_T surface (mpit.py) is only as trustworthy as the declarations
feeding it. Three invariants, all checkable syntactically because the
registry idiom is declarative (utils/config.cvar, mpit.pvar):

  * every pvar FETCHED anywhere (a 1/2-argument ``pvar("name")`` call —
    the bump-side idiom) is DECLARED somewhere in the scanned set (a
    call carrying class/group/desc), so a typo'd counter name can never
    silently mint an undeclared, undocumented pvar;
  * every ``MV2T_*`` environment read resolves to a declared cvar —
    knobs must go through the config registry so ``mpiname -a`` /
    MPI_T enumeration stays complete. Launcher<->child wire-protocol
    plumbing (rank/size/KVS coordinates, not knobs) is exempted via
    INTERNAL_ENV; config-registry reads (``get_config()[...]``) must
    name a declared cvar too;
  * names follow convention: pvars lower_snake, cvars UPPER_SNAKE.

Dynamic keys (f-strings like ``MV2T_DEBUG_<subsys>``) are out of static
reach; the exempt prefixes below cover the two families in use.

The env-drift doctor extends the same invariant to the NON-python
surfaces: every ``getenv("MV2T_*")`` in the native C sources and every
``MV2T_*`` token in bin/ scripts and the README must resolve to a
declared cvar (or the internal-plumbing exemptions) — a documented knob
with no registration, or a native env read the registry never heard of,
is exactly the doc/env drift that makes ``mpiname -a`` lie.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, LintPass, REPO_ROOT, SourceModule, attr_chain

# launcher<->child wire plumbing: process coordinates, not tunables
INTERNAL_ENV: Set[str] = {
    "MV2T_RANK", "MV2T_SIZE", "MV2T_KVS", "MV2T_FAKE_NODE", "MV2T_FT",
    "MV2T_WORLD_BASE", "MV2T_SPAWN_CTX", "MV2T_APPNUM",
    "MV2T_PARENT_RANKS", "MV2T_RANK_PLATFORM", "MV2T_PLATFORM_EXPLICIT",
    "MV2T_VPOD_CHILD", "MV2T_TEST_FULL", "MV2T_FT_WATCHER",
    # sanitizer-lane plumbing (bin/runtests --tsan): points every ring
    # consumer in the job at one instrumented variant .so — a build
    # coordinate, not a tunable
    "MV2T_SHMRING_SO",
    # toolchain coordinates of the compiler wrappers (bin/mpicc and
    # friends): which cc/f90 to exec, not runtime knobs
    "MV2T_CC", "MV2T_CXX", "MV2T_FC",
}
# MV2T_MET_*: the metrics-segment layout #define namespace
# (native/shm_layout.h, doc-referenced) — cross-language constants
# pinned by the layout doctor, not env tunables
INTERNAL_PREFIXES = ("MV2T_DEBUG_", "MV2T_MET_")

# env-drift doctor: the committed non-python surfaces scanned by
# default (native getenv reads; MV2T_* tokens in bin/ and the README)
_DOC_NATIVE_DIR = os.path.join(REPO_ROOT, "native")
_DOC_BIN_DIR = os.path.join(REPO_ROOT, "bin")
_DOC_README = os.path.join(REPO_ROOT, "README.md")
_GETENV_RE = re.compile(r'getenv\(\s*"(MV2T_[A-Z0-9_]*)"')
_TOKEN_RE = re.compile(r"\bMV2T_[A-Z0-9_]*")

_PVAR_RE = re.compile(r"^[a-z][a-z0-9_]*$")
_CVAR_RE = re.compile(r"^[A-Z][A-Z0-9_]*$")
_DECL_KWARGS = {"klass", "group", "desc", "source"}
_CFG_RECEIVERS = {"cfg", "config", "_config"}


def _str_arg0(call: ast.Call):
    if call.args and isinstance(call.args[0], ast.Constant) \
            and isinstance(call.args[0].value, str):
        return call.args[0].value
    return None


def _is_config_receiver(node: ast.AST) -> bool:
    """get_config() / get_config().cvars-free receiver / cfg / config."""
    if isinstance(node, ast.Call):
        chain = attr_chain(node.func)
        return chain is not None and chain.endswith("get_config")
    if isinstance(node, ast.Name):
        return node.id in _CFG_RECEIVERS
    return False


def _is_environ(node: ast.AST) -> bool:
    chain = attr_chain(node)
    return chain is not None and chain.split(".")[-1] == "environ"


def _default_doc_sources() -> List[str]:
    out: List[str] = []
    for d, exts in ((_DOC_NATIVE_DIR, (".c", ".cpp", ".cc", ".h")),
                    (_DOC_BIN_DIR, None)):
        try:
            names = sorted(os.listdir(d))
        except OSError:
            continue
        for f in names:
            p = os.path.join(d, f)
            if not os.path.isfile(p):
                continue
            if exts is None or f.endswith(exts):
                out.append(p)
    if os.path.exists(_DOC_README):
        out.append(_DOC_README)
    return out


class RegistryPass(LintPass):
    id = "pvars"
    doc = ("pvars fetched anywhere must be declared; MV2T_* env reads "
           "(python, native getenv, bin/ scripts, README) must have a "
           "declared cvar; names follow convention")

    def __init__(self, doc_sources: Optional[List[str]] = None):
        # doc_sources: non-python surfaces for the env-drift doctor;
        # None = the committed native/bin/README set, [] disables
        self.doc_sources = doc_sources

    def run(self, modules: List[SourceModule]) -> List[Finding]:
        out: List[Finding] = []
        declared_pvars: Set[str] = set()
        declared_cvars: Set[str] = set()
        dynamic_cvar_pats: List[re.Pattern] = []
        pvar_uses: List[Tuple[SourceModule, int, str]] = []
        env_reads: List[Tuple[SourceModule, int, str]] = []
        cfg_reads: List[Tuple[SourceModule, int, str]] = []
        decl_sites: Dict[str, Tuple[SourceModule, int]] = {}

        for mod in modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, (ast.Call, ast.Subscript)):
                    continue
                if isinstance(node, ast.Subscript):
                    if not isinstance(node.ctx, ast.Load):
                        continue
                    key = node.slice
                    if not (isinstance(key, ast.Constant)
                            and isinstance(key.value, str)):
                        continue
                    if _is_environ(node.value) \
                            and key.value.startswith("MV2T_"):
                        env_reads.append((mod, node.lineno, key.value))
                    elif _is_config_receiver(node.value):
                        cfg_reads.append((mod, node.lineno, key.value))
                    continue
                fn = node.func
                name = fn.attr if isinstance(fn, ast.Attribute) else \
                    (fn.id if isinstance(fn, ast.Name) else None)
                if name == "pvar":
                    pname = _str_arg0(node)
                    if pname is None:
                        continue
                    is_decl = len(node.args) >= 3 or \
                        any(kw.arg in _DECL_KWARGS for kw in node.keywords)
                    if is_decl:
                        declared_pvars.add(pname)
                        decl_sites.setdefault(f"p:{pname}",
                                              (mod, node.lineno))
                    else:
                        pvar_uses.append((mod, node.lineno, pname))
                elif name == "cvar" or (name == "declare"
                                        and isinstance(fn, ast.Attribute)):
                    cname = _str_arg0(node)
                    if cname is None:
                        # a loop-generated family (cvar(f"{_c}_ALGO")):
                        # the constant parts become a match pattern so
                        # doc mentions of family members still resolve
                        if node.args and isinstance(node.args[0],
                                                    ast.JoinedStr):
                            parts = [re.escape(v.value)
                                     if isinstance(v, ast.Constant)
                                     else "[A-Z0-9_]+"
                                     for v in node.args[0].values]
                            dynamic_cvar_pats.append(
                                re.compile("^" + "".join(parts) + "$"))
                        continue
                    declared_cvars.add(cname)
                    decl_sites.setdefault(f"c:{cname}", (mod, node.lineno))
                elif name == "get" and isinstance(fn, ast.Attribute):
                    key = _str_arg0(node)
                    if key is None:
                        continue
                    if _is_environ(fn.value) and key.startswith("MV2T_"):
                        env_reads.append((mod, node.lineno, key))
                    elif _is_config_receiver(fn.value):
                        cfg_reads.append((mod, node.lineno, key))

        def emit(mod: SourceModule, line: int, msg: str) -> None:
            f = self.finding(mod, line, msg)
            if f is not None:
                out.append(f)

        for pname in sorted(declared_pvars):
            if not _PVAR_RE.match(pname):
                mod, line = decl_sites[f"p:{pname}"]
                emit(mod, line, f"pvar '{pname}' violates the lower_snake "
                     "naming convention")
        for cname in sorted(declared_cvars):
            if not _CVAR_RE.match(cname):
                mod, line = decl_sites[f"c:{cname}"]
                emit(mod, line, f"cvar '{cname}' violates the UPPER_SNAKE "
                     "naming convention")
        seen: Set[str] = set()
        for mod, line, pname in pvar_uses:
            if pname not in declared_pvars and pname not in seen:
                seen.add(pname)
                emit(mod, line, f"pvar '{pname}' is fetched but never "
                     "declared (no klass/group/desc registration in the "
                     "scanned set)")
        for mod, line, env in env_reads:
            if env in INTERNAL_ENV or env.startswith(INTERNAL_PREFIXES):
                continue
            if env[len("MV2T_"):] not in declared_cvars:
                emit(mod, line, f"env read '{env}' has no declared cvar "
                     "(declare it with utils.config.cvar or add it to "
                     "INTERNAL_ENV)")
        for mod, line, key in cfg_reads:
            if key not in declared_cvars:
                emit(mod, line, f"config read '{key}' names no declared "
                     "cvar")

        # -- env-drift doctor over the non-python surfaces --------------
        def known(env: str) -> bool:
            if env in INTERNAL_ENV or env.startswith(INTERNAL_PREFIXES):
                return True
            name = env[len("MV2T_"):].rstrip("_")
            if not name:
                return True          # a bare 'MV2T_' prefix mention
            return name in declared_cvars \
                or any(p.match(name) for p in dynamic_cvar_pats)

        doc_sources = self.doc_sources
        if doc_sources is None:
            # only meaningful against the full package: the committed
            # docs resolve against the whole cvar registry, not a
            # fixture's subset
            if any(m.relpath.endswith("mvapich2_tpu/mpit.py")
                   for m in modules):
                doc_sources = _default_doc_sources()
            else:
                doc_sources = []
        seen_doc: Set[str] = set()
        for path in doc_sources:
            try:
                with open(path, encoding="utf-8") as fh:
                    text = fh.read()
            except (OSError, UnicodeDecodeError):
                continue
            native = path.endswith((".c", ".cpp", ".cc", ".h"))
            matcher = _GETENV_RE if native else _TOKEN_RE
            rel = os.path.relpath(path, REPO_ROOT)
            if rel.startswith(".."):
                rel = os.path.basename(path)
            for i, line_text in enumerate(text.splitlines(), start=1):
                for m in matcher.finditer(line_text):
                    env = m.group(1) if native else m.group(0)
                    if known(env) or (rel, env) in seen_doc:
                        continue
                    seen_doc.add((rel, env))
                    where = "native getenv" if native else "mention"
                    out.append(Finding(
                        self.id, rel, i,
                        f"{where} '{env}' has no declared cvar — "
                        "register it (utils.config.cvar) or add it to "
                        "INTERNAL_ENV"))
        return out
