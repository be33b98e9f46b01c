"""Shared machinery for the invariant analyzers.

The suite is AST-only: no analyzed module is ever imported, so fixture
files with deliberately broken concurrency and framework modules with
heavyweight imports analyze identically.  Each checker consumes
``SourceModule`` objects and emits ``Finding``s; suppression comments
(``# ktpu: allow(<rule>) — <reason>``) are resolved here, uniformly, so
a checker never needs to know it was silenced.

A suppression without a reason is itself a finding (``bare-suppression``)
— the suppression syntax exists to FORCE the justification into the
diff, not to provide an escape hatch from it.
"""

from __future__ import annotations

import ast
import json
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence

RULE_LOCK = "lock-discipline"
RULE_PURITY = "plugin-purity"
RULE_JIT = "jit-boundary"
RULE_D2H = "d2h-leak"
RULE_DONATION = "donation"
RULE_CLAMP = "slice-clamp"
RULE_RETRACE = "retrace"
RULE_SHAPE = "shape"
RULE_DTYPE = "dtype"
RULE_SHARD = "shard"
RULE_BREAKER = "breaker"
RULE_BARE_SUPPRESSION = "bare-suppression"

ALL_RULES = (
    RULE_LOCK,
    RULE_PURITY,
    RULE_JIT,
    RULE_D2H,
    RULE_DONATION,
    RULE_CLAMP,
    RULE_RETRACE,
    RULE_SHAPE,
    RULE_DTYPE,
    RULE_SHARD,
    RULE_BREAKER,
    RULE_BARE_SUPPRESSION,
)

# `# ktpu: allow(rule[, rule...]) — reason`  (em/en/double/single dash or
# colon all accepted as the reason separator; the reason is mandatory)
_SUPPRESS_RE = re.compile(
    r"#\s*ktpu:\s*allow\(\s*([a-zA-Z0-9_,\- ]+?)\s*\)\s*(?:(?:—|–|--|-|:)\s*(\S.*))?$"
)


@dataclass
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def as_dict(self) -> Dict[str, object]:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
        }


@dataclass
class Suppression:
    rules: List[str]
    line: int
    reason: str  # "" when bare
    used: bool = False


class SourceModule:
    """One parsed file: source lines, AST, and its suppression table."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        # line → suppressions; comments alone on their lines (STACKABLE —
        # one per rule with its own reason) cover the next non-comment
        # line, a trailing comment covers its own line.
        self.suppressions: Dict[int, List[Suppression]] = {}
        self.bare_suppressions: List[int] = []
        self._scan_suppressions()

    @classmethod
    def load(cls, path: str) -> "SourceModule":
        with open(path, "r", encoding="utf-8") as f:
            return cls(path, f.read())

    def _scan_suppressions(self) -> None:
        pending: List[Suppression] = []
        for i, raw in enumerate(self.lines, start=1):
            stripped = raw.strip()
            m = _SUPPRESS_RE.search(raw)
            if m:
                rules = [r.strip() for r in m.group(1).split(",") if r.strip()]
                reason = (m.group(2) or "").strip()
                sup = Suppression(rules=rules, line=i, reason=reason)
                if not reason:
                    self.bare_suppressions.append(i)
                if stripped.startswith("#"):
                    pending.append(sup)  # standalone → covers next code line
                else:
                    self.suppressions.setdefault(i, []).append(sup)
                continue
            if pending and stripped and not stripped.startswith("#"):
                self.suppressions.setdefault(i, []).extend(pending)
                pending = []

    def suppressed(self, rule: str, line: int) -> bool:
        for sup in self.suppressions.get(line, ()):
            if rule in sup.rules and sup.reason:
                sup.used = True
                return True
        return False


# Process-level parse cache: every rule family reads the same shipped-tree
# files, and the tier-1 gate runs the whole suite dozens of times per
# session (tree gate + every fixture case + the CLI tests).  One parse
# per (path, content digest) serves all of them; a touched file
# (fixtures written to tmp dirs, editor saves between runs) misses on
# content and reparses.  Suppression hit-tracking is the
# only mutable state on a SourceModule and is monotonic, so sharing
# instances across rule families and runs is safe.
_SOURCE_CACHE: Dict[str, tuple] = {}


def load_source(path: str) -> SourceModule:
    """Content-keyed cached parse — the single AST share point for all
    rule families (each checker used to load its own copy).  Keyed on a
    digest of the bytes, not mtime: a rewrite within the filesystem
    timestamp granularity (write→analyze→write→analyze loops in one
    process) must never serve the stale AST.  The read+hash is the cheap
    part; it's the ast.parse the cache amortizes."""
    import hashlib
    import os

    key = os.path.abspath(path)
    with open(key, "rb") as f:
        raw = f.read()
    digest = hashlib.blake2b(raw, digest_size=16).digest()
    hit = _SOURCE_CACHE.get(key)
    if hit is not None and hit[0] == digest:
        return hit[1]
    mod = SourceModule(key, raw.decode("utf-8"))
    _SOURCE_CACHE[key] = (digest, mod)
    return mod


def dotted_name(node: ast.AST) -> Optional[str]:
    """Flatten Name/Attribute chains to 'a.b.c' (None for anything else)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(node: ast.Call) -> Optional[str]:
    return dotted_name(node.func)


class ImportRefs:
    """Module-wide import tables (module-level AND function-local imports —
    the scheduler defers most ops imports into the methods that use them).

    ``mod_alias`` maps a local name to an in-package MODULE's base name
    (``from kubernetes_tpu.ops import fastpath as ops_fp`` → ``ops_fp`` →
    ``'fastpath'``); ``sym_alias`` maps a local name to ``(module base,
    symbol)`` for direct symbol imports.  Module-vs-symbol is decided by
    the package's own convention: modules are lowercase and imported from
    a package path at most two levels deep.
    """

    def __init__(self, tree: ast.Module):
        self.mod_alias: Dict[str, str] = {}
        self.sym_alias: Dict[str, tuple] = {}
        self.np_roots: set = set()
        self.jnp_roots: set = set()
        self.jax_roots: set = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    local = a.asname or a.name.split(".")[0]
                    if a.name == "numpy":
                        self.np_roots.add(local)
                    elif a.name == "jax.numpy" and a.asname:
                        self.jnp_roots.add(a.asname)
                    elif a.name == "jax" or a.name.startswith("jax."):
                        self.jax_roots.add(local)
            elif isinstance(node, ast.ImportFrom):
                m = node.module or ""
                for a in node.names:
                    local = a.asname or a.name
                    if m == "numpy":
                        self.np_roots.add(local)
                    elif m == "jax" and a.name == "numpy":
                        self.jnp_roots.add(local)
                    elif m == "jax":
                        self.jax_roots.add(local)
                    elif m == "kubernetes_tpu" or m.startswith("kubernetes_tpu."):
                        if a.name[:1].islower() and m.count(".") <= 1:
                            self.mod_alias[local] = a.name
                        else:
                            self.sym_alias[local] = (m.rsplit(".", 1)[-1], a.name)


def resolve_root(refs: ImportRefs, self_roots: dict, roots_by_base: dict,
                 func: ast.AST):
    """Resolve a call target to a registered root through the import
    alias tables — shared by the donation and retrace checkers.

    ``self_roots`` is the CURRENT module's own name→root table, scoped by
    PATH (two target modules sharing a basename — ops/explain.py and
    observability/explain.py — must not resolve each other's bare names);
    ``roots_by_base`` is the module-base-keyed table the sym/mod alias
    lookups go through (import paths only carry the base)."""
    dn = dotted_name(func)
    if dn is None:
        return None
    parts = dn.split(".")
    if len(parts) == 1:
        r = self_roots.get(parts[0])
        if r is not None:
            return r
        if parts[0] in refs.sym_alias:
            m, s = refs.sym_alias[parts[0]]
            return roots_by_base.get(m, {}).get(s)
        return None
    if len(parts) == 2 and parts[0] in refs.mod_alias:
        return roots_by_base.get(refs.mod_alias[parts[0]], {}).get(parts[1])
    return None


def module_literal(tree: ast.Module, name: str):
    """Evaluate a module-level literal assignment (the annotation registry
    pattern: ``_KTPU_GUARDED = {...}``) without importing the module."""
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    try:
                        return ast.literal_eval(node.value)
                    except (ValueError, SyntaxError):
                        return None
    return None


class Checker:
    """Base: run() yields raw findings; filter_findings applies suppressions
    from the owning module."""

    rule: str = ""

    def __init__(self) -> None:
        self.findings: List[Finding] = []

    def emit(self, mod: SourceModule, line: int, message: str, rule: Optional[str] = None) -> None:
        r = rule or self.rule
        if not mod.suppressed(r, line):
            self.findings.append(Finding(r, mod.path, line, message))


def collect_bare_suppressions(mods: Iterable[SourceModule]) -> List[Finding]:
    out = []
    for mod in mods:
        for line in mod.bare_suppressions:
            out.append(
                Finding(
                    RULE_BARE_SUPPRESSION,
                    mod.path,
                    line,
                    "suppression without a justification — write "
                    "`# ktpu: allow(<rule>) — <reason>`",
                )
            )
    return out


def render_text(findings: Sequence[Finding]) -> str:
    if not findings:
        return "kubernetes_tpu.analysis: no findings"
    lines = [f.format() for f in findings]
    lines.append(f"kubernetes_tpu.analysis: {len(findings)} finding(s)")
    return "\n".join(lines)


def render_json(
    findings: Sequence[Finding],
    rule_seconds: Optional[Dict[str, float]] = None,
    baseline_suppressed: Optional[int] = None,
) -> str:
    by_rule: Dict[str, int] = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    doc: Dict[str, object] = {
        "findings": [f.as_dict() for f in findings],
        "count": len(findings),
        "by_rule": by_rule,
    }
    if rule_seconds is not None:
        # per-rule wall time; the shape/dtype/shard families share one
        # symbolic interpretation whose cost lands on whichever ran
        # first ('shape' — see run_analysis)
        doc["rule_seconds"] = {
            k: round(v, 4) for k, v in rule_seconds.items()
        }
    if baseline_suppressed is not None:
        doc["baseline_suppressed"] = baseline_suppressed
    return json.dumps(doc, indent=2, sort_keys=True)
