"""gslint — the project invariant checker.

Every perf and robustness PR in this repo depends on hand-enforced
invariants: no host↔device sync outside the sanctioned egress sites
(the dispatch wall: in earlier rounds' ladders the round-trip, not
compute, was the bottleneck), no impure reads inside
traced code (an `os.environ` read under `jax.jit` silently freezes at
compile time), every `GS_*` knob through the typed registry
(utils/knobs.py), every failure recorded durably, shared state
lock-guarded, checkpoint formats symmetric. Discipline that isn't
mechanically checked erodes; this package is the mechanical check —
an AST-based rule suite specific to this codebase, run as a tier-1
test (tests/test_gslint.py, marker `lint`) so a violation is a test
failure before it is a 2am chip-window debugging session.

Rules (tools/gslint/rules.py):

    R1 host-sync     d2h sync surface (`np.asarray` / `jax.device_get`
                     / `.item()` / `block_until_ready` / `float()`-of-
                     device-expressions) outside the sanctioned
                     egress/finalize/mirror-sync modules
    R2 jit-purity    impure reads (env, telemetry, clocks, module
                     mutables) reachable from jit/scan/shard_map roots
    R3 knob-registry `os.environ` outside utils/knobs.py, unregistered
                     `GS_*` literals, README knob-table drift
    R4 except-hygiene broad/bare excepts that swallow silently
    R5 thread-shared module-level mutables in threaded modules without
                     a lock-guarded access pattern
    R6 ckpt-symmetry state_dict/load_state_dict key-set mismatches

Suppression, narrowest first:

- inline pragma `# gslint: disable=<rule-or-name>[,...]` on the
  flagged line (use for sites with a REASON — put it in a comment);
- file pragma `# gslint: disable-file=<rule>[,...]` anywhere in the
  file's first comment block;
- the committed baseline (tools/gslint/baseline.json): grandfathered
  pre-gslint sites, keyed by (rule, path, enclosing symbol, code
  text) — line-number drift does not invalidate entries, edits to
  the flagged line do. The baseline only ever shrinks: regenerating
  it (`--write-baseline`) to absorb NEW findings defeats the tool,
  and tests/test_gslint.py pins its size.

Usage:
    python -m tools.gslint gelly_streaming_tpu        # human output
    python -m tools.gslint --json -                   # machine output
    python -m tools.gslint --write-baseline           # (re)generate
    python -m tools.gslint --knob-table               # README table

Exit status: number of non-baselined findings, capped at 125 (0 =
clean). The runner reads only committed source files — no runtime
state, no imports of the package under lint (utils/knobs.py is loaded
standalone by file path for the R3 docs diff) — so a soak or bench
run can never change its verdict (pinned by tools/chaos_run.py's
gslint-hermetic leg).
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_TARGET = "gelly_streaming_tpu"
BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline.json")

_PRAGMA_RE = re.compile(r"#\s*gslint:\s*disable=([A-Za-z0-9_,\- ]+)")
_FILE_PRAGMA_RE = re.compile(
    r"#\s*gslint:\s*disable-file=([A-Za-z0-9_,\- ]+)")


@dataclass
class Finding:
    """One rule violation at one source location. `symbol` (the
    enclosing def/class qualname) and `code` (the stripped source
    line) — not the line number — form the baseline identity, so
    unrelated edits above a grandfathered site don't resurrect it."""

    rule: str        # "R1".."R6"
    name: str        # rule slug, e.g. "host-sync"
    path: str        # repo-relative posix path
    line: int
    col: int
    message: str
    symbol: str = "<module>"
    code: str = ""
    baselined: bool = False

    def key(self):
        return (self.rule, self.path, self.symbol, self.code)

    def to_json(self) -> dict:
        return {
            "rule": self.rule, "name": self.name, "path": self.path,
            "line": self.line, "col": self.col,
            "message": self.message, "symbol": self.symbol,
            "code": self.code, "baselined": self.baselined,
        }

    def render(self) -> str:
        mark = "  [baseline]" if self.baselined else ""
        return "%s:%d:%d: %s[%s] %s (in %s)%s" % (
            self.path, self.line, self.col, self.rule, self.name,
            self.message, self.symbol, mark)


class Rule:
    """One invariant. Subclasses set `rule_id`/`name`/`doc` and
    implement `check_module` (per-file findings) and/or
    `check_project` (whole-tree findings, e.g. the README docs
    diff)."""

    rule_id = "R0"
    name = "base"
    doc = ""

    def check_module(self, ctx: "ModuleCtx") -> List["Finding"]:
        return []

    def check_project(self, ctxs: Sequence["ModuleCtx"],
                      repo: str) -> List["Finding"]:
        return []

    def finding(self, ctx: "ModuleCtx", node, message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        return Finding(
            rule=self.rule_id, name=self.name, path=ctx.path,
            line=line, col=col, message=message,
            symbol=ctx.symbol_at(line),
            code=ctx.code_at(line))


@dataclass
class ModuleCtx:
    """Parsed view of one source file handed to every rule: the AST,
    the raw lines, per-line pragma sets, and a line→enclosing-symbol
    index (built once; rules are read-only consumers)."""

    path: str                 # repo-relative posix
    tree: ast.AST
    lines: List[str]
    pragmas: Dict[int, set] = field(default_factory=dict)
    file_pragmas: set = field(default_factory=set)
    _symbols: List[tuple] = field(default_factory=list)

    @classmethod
    def load(cls, abspath: str, relpath: str) -> Optional["ModuleCtx"]:
        with open(abspath, encoding="utf-8") as f:
            source = f.read()
        try:
            tree = ast.parse(source, filename=relpath)
        except SyntaxError:
            return None  # not ours to judge; python itself will
        ctx = cls(path=relpath.replace(os.sep, "/"), tree=tree,
                  lines=source.splitlines())
        for i, text in enumerate(ctx.lines, 1):
            m = _PRAGMA_RE.search(text)
            if m:
                ctx.pragmas[i] = {t.strip() for t in
                                  m.group(1).split(",") if t.strip()}
            m = _FILE_PRAGMA_RE.search(text)
            if m:
                ctx.file_pragmas |= {t.strip() for t in
                                     m.group(1).split(",") if t.strip()}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                end = getattr(node, "end_lineno", node.lineno)
                ctx._symbols.append((node.lineno, end, node.name,
                                     isinstance(node, ast.ClassDef)))
        ctx._symbols.sort()
        return ctx

    def code_at(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def symbol_at(self, line: int) -> str:
        """Innermost enclosing def/class name chain ('Cls.meth'), or
        '<module>'."""
        chain = []
        for start, end, name, _is_cls in self._symbols:
            if start <= line <= end:
                chain.append((start, name))
        if not chain:
            return "<module>"
        chain.sort()
        return ".".join(name for _s, name in chain[-2:])

    def suppressed(self, f: Finding) -> bool:
        tags = self.pragmas.get(f.line, set()) | self.file_pragmas
        return bool(tags & {f.rule, f.name, "all"})


# ----------------------------------------------------------------------
# baseline
# ----------------------------------------------------------------------
def load_baseline(path: str = BASELINE_PATH) -> Dict[tuple, int]:
    """Counted multiset of grandfathered finding keys. Missing file =
    empty baseline (the self-check fixtures run baseline-free)."""
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    out: Dict[tuple, int] = {}
    for e in data.get("entries", []):
        key = (e["rule"], e["path"], e["symbol"], e["code"])
        out[key] = out.get(key, 0) + int(e.get("count", 1))
    return out


def apply_baseline(findings: List[Finding],
                   baseline: Dict[tuple, int]) -> None:
    """Mark findings covered by the baseline, consuming counts so N
    grandfathered copies of a line never absolve an N+1th."""
    budget = dict(baseline)
    for f in findings:
        if budget.get(f.key(), 0) > 0:
            budget[f.key()] -= 1
            f.baselined = True


def write_baseline(findings: List[Finding],
                   path: str = BASELINE_PATH) -> int:
    counts: Dict[tuple, int] = {}
    for f in findings:
        counts[f.key()] = counts.get(f.key(), 0) + 1
    entries = [
        {"rule": k[0], "path": k[1], "symbol": k[2], "code": k[3],
         "count": n}
        for k, n in sorted(counts.items())
    ]
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "entries": entries}, f, indent=1)
        f.write("\n")
    return len(entries)


# ----------------------------------------------------------------------
# runner
# ----------------------------------------------------------------------
def iter_sources(target: str, repo: str = REPO):
    """Yield (abspath, repo-relative path) for every committed .py
    under `target` (itself repo-relative or absolute)."""
    root = target if os.path.isabs(target) else os.path.join(repo,
                                                             target)
    if os.path.isfile(root):
        yield root, os.path.relpath(root, repo)
        return
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d != "__pycache__"
                             and not d.startswith("."))
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                ap = os.path.join(dirpath, fn)
                yield ap, os.path.relpath(ap, repo)


def run_lint(targets: Sequence[str] = (DEFAULT_TARGET,),
             rules: Optional[Sequence[Rule]] = None,
             baseline_path: Optional[str] = BASELINE_PATH,
             repo: str = REPO) -> List[Finding]:
    """Lint `targets`, returning ALL findings (pragma-suppressed ones
    dropped, baselined ones marked). Deterministic: sorted file walk,
    stable rule order, no clocks, no randomness, no imports of the
    code under lint."""
    from . import rules as rules_mod

    if rules is None:
        rules = rules_mod.all_rules()
    ctxs: List[ModuleCtx] = []
    for target in targets:
        for abspath, rel in iter_sources(target, repo):
            ctx = ModuleCtx.load(abspath, rel)
            if ctx is not None:
                ctxs.append(ctx)
    findings: List[Finding] = []
    by_path = {c.path: c for c in ctxs}
    for rule in rules:
        for ctx in ctxs:
            for f in rule.check_module(ctx):
                if not ctx.suppressed(f):
                    findings.append(f)
        for f in rule.check_project(ctxs, repo):
            ctx = by_path.get(f.path)
            if ctx is None or not ctx.suppressed(f):
                findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    if baseline_path:
        apply_baseline(findings, load_baseline(baseline_path))
    return findings


def report_json(findings: List[Finding],
                targets: Sequence[str]) -> dict:
    per_rule: Dict[str, int] = {}
    for f in findings:
        if not f.baselined:
            per_rule[f.rule] = per_rule.get(f.rule, 0) + 1
    return {
        "version": 1,
        "tool": "gslint",
        "targets": list(targets),
        "findings": [f.to_json() for f in findings],
        "counts": {
            "total": len(findings),
            "baselined": sum(1 for f in findings if f.baselined),
            "new": sum(1 for f in findings if not f.baselined),
            "per_rule": per_rule,
        },
    }


# ----------------------------------------------------------------------
# report schema (tools/perf_schema.py conventions: known shapes are
# enforced, unknown top-level keys are allowed)
# ----------------------------------------------------------------------
_FINDING_KEYS = {
    "rule": str, "name": str, "path": str, "line": int, "col": int,
    "message": str, "symbol": str, "code": str, "baselined": bool,
}


def validate_report(obj) -> List[str]:
    """Shape-check one report_json() payload; returns problem strings
    (empty = clean). Same contract style as tools/perf_schema.py:
    consumers (CI diffing, trend dashboards) must never crash on a
    committed report."""
    errors: List[str] = []
    if not isinstance(obj, dict):
        return ["report: not an object"]
    if obj.get("version") != 1:
        errors.append("report: version must be 1")
    if obj.get("tool") != "gslint":
        errors.append("report: tool must be 'gslint'")
    if not isinstance(obj.get("targets"), list):
        errors.append("report: targets must be a list")
    findings = obj.get("findings")
    if not isinstance(findings, list):
        errors.append("report: findings must be a list")
        findings = []
    for i, f in enumerate(findings):
        if not isinstance(f, dict):
            errors.append("findings[%d]: not an object" % i)
            continue
        for key, kind in _FINDING_KEYS.items():
            if key not in f:
                errors.append("findings[%d]: missing %s" % (i, key))
            elif not isinstance(f[key], kind):
                errors.append("findings[%d].%s: expected %s, got %r"
                              % (i, key, kind.__name__, f[key]))
        rule = f.get("rule")
        if isinstance(rule, str) and not re.fullmatch(r"R[1-6]", rule):
            errors.append("findings[%d].rule: unknown rule %r"
                          % (i, rule))
    counts = obj.get("counts")
    if not isinstance(counts, dict):
        errors.append("report: counts must be an object")
    else:
        for key in ("total", "baselined", "new"):
            if not isinstance(counts.get(key), int):
                errors.append("counts.%s: expected int" % key)
        if not isinstance(counts.get("per_rule"), dict):
            errors.append("counts.per_rule: expected object")
        elif isinstance(counts.get("new"), int):
            if sum(counts["per_rule"].values()) != counts["new"]:
                errors.append("counts.per_rule: does not sum to new")
    return errors
