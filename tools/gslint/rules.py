"""The six project invariants, as AST rules over committed source.

Each rule is deliberately PROJECT-SPECIFIC: the module sets and call
surfaces below encode this repo's architecture (DESIGN.md §13), not a
general Python style. False positives are handled by the pragma /
baseline machinery in tools/gslint/__init__.py, so rules here lean
toward catching the failure shape over statistical precision.
"""

from __future__ import annotations

import ast
import importlib.util
import os
import re
from typing import Dict, List, Optional, Sequence, Set

from . import Finding, ModuleCtx, Rule

PKG = "gelly_streaming_tpu"


def _dotted(node) -> str:
    """'a.b.c' for nested Attribute/Name chains, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _imports_jax(tree: ast.AST) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            if any(a.name.split(".")[0] == "jax" for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "").split(".")[0] == "jax":
                return True
    return False


# ======================================================================
# R1 — host-sync discipline
# ======================================================================
class HostSyncRule(Rule):
    """Host↔device synchronization is the dispatch wall (in earlier
    rounds' ladders the round-trip, not compute, bounded the device
    path). Every d2h
    materialization must happen at a sanctioned egress/finalize/
    mirror-sync site — the driver's delivery boundary, the delta
    egress decode, the host-twin mirror sync — where it is batched,
    telemetry-covered, and demotion-aware. A stray `np.asarray(...)`
    on a device value anywhere else inserts an unaccounted sync point
    that the megakernel/Pallas refactors will silently inherit.

    Scope: modules that import jax (elsewhere `np.asarray` is
    numpy-on-numpy, not a sync), minus the sanctioned modules."""

    rule_id = "R1"
    name = "host-sync"
    doc = ("d2h sync surface calls outside the sanctioned "
           "egress/finalize/mirror-sync modules")

    SANCTIONED = (
        PKG + "/core/driver.py",       # delivery/finalize boundary
        PKG + "/ops/delta_egress.py",  # the egress wire itself
        PKG + "/parallel/host_twin.py",  # mirror sync / demotion
    )
    # attribute-call surface: full dotted suffixes
    SYNC_CALLS = {
        "np.asarray", "np.array", "numpy.asarray", "numpy.array",
        "jax.device_get", "device_get",
    }
    SYNC_METHODS = {"item", "block_until_ready"}

    def check_module(self, ctx: ModuleCtx) -> List[Finding]:
        if not ctx.path.startswith(PKG + "/"):
            return []
        if ctx.path in self.SANCTIONED:
            return []
        if not _imports_jax(ctx.tree):
            return []
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            hit = None
            if dotted in self.SYNC_CALLS:
                hit = dotted
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in self.SYNC_METHODS
                  and not node.args and not node.keywords):
                hit = ".%s()" % node.func.attr
            elif (isinstance(node.func, ast.Name)
                  and node.func.id in ("float", "int")
                  and len(node.args) == 1
                  and isinstance(node.args[0],
                                 (ast.Subscript, ast.Call))):
                # float(x[w]) / int(dev()) — the forced-scalar shape;
                # plain float(name) is everyday host arithmetic
                hit = "%s(<device expr>)" % node.func.id
            if hit:
                out.append(self.finding(
                    ctx, node,
                    "host-sync surface call %s outside a sanctioned "
                    "egress site — batch it through the driver "
                    "delivery boundary, ops/delta_egress, or the "
                    "parallel/host_twin mirror sync" % hit))
        return out


# ======================================================================
# R2 — jit purity
# ======================================================================
class JitPurityRule(Rule):
    """Anything reachable from a jit/scan/shard_map root executes at
    TRACE time: an `os.environ` read there silently freezes the
    knob's value into the compiled program (flipping it mid-process —
    which tests and tools/chaos_run.py do — then changes nothing), a
    clock or telemetry call records trace time once instead of run
    time, and a module-level mutable read bakes in whatever the first
    trace saw. Roots: @jit decorators, jit()/lax.scan/lax.map/
    while_loop/fori_loop/cond/shard_map call sites; reachability is
    name-resolved within the module (conservative but deterministic).
    """

    rule_id = "R2"
    name = "jit-purity"
    doc = ("impure reads (env/clock/telemetry/module mutables) "
           "reachable from traced code")

    _JIT_WRAP = {"jit", "jax.jit"}
    # callable-argument positions of the traced-control-flow surface
    _TRACED_ARGS = {
        "lax.scan": (0,), "jax.lax.scan": (0,),
        "lax.map": (0,), "jax.lax.map": (0,),
        "lax.while_loop": (0, 1), "jax.lax.while_loop": (0, 1),
        "lax.fori_loop": (2,), "jax.lax.fori_loop": (2,),
        "lax.cond": (1, 2), "jax.lax.cond": (1, 2),
        "shard_map": (0,), "shard_map_norep": (0,),
        "jax.shard_map": (0,),
        # Pallas kernel bodies trace like any jit root (and freeze
        # even harder: the kernel compiles once per shape into a
        # Mosaic binary) — ops/pallas_window.py and the seed kernels
        # are in-scope via their pallas_call sites
        "pl.pallas_call": (0,), "pallas_call": (0,),
        "pallas.pallas_call": (0,),
    }
    _CLOCK_CALLS = {"time.time", "time.perf_counter", "time.monotonic",
                    "time.sleep", "time.process_time"}

    def check_module(self, ctx: ModuleCtx) -> List[Finding]:
        if not ctx.path.startswith(PKG + "/"):
            return []
        defs: Dict[str, ast.AST] = {}
        mutables: Set[str] = set()
        for node in ctx.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs[node.name] = node
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        defs.setdefault(sub.name, sub)
            elif isinstance(node, ast.Assign):
                if self._is_mutable_literal(node.value):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            mutables.add(t.id)
                if isinstance(node.value, ast.Lambda):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            defs[t.id] = node.value
        roots = self._roots(ctx, defs)
        reached: List[ast.AST] = []
        seen: Set[int] = set()
        queue = list(roots)
        while queue:
            fn = queue.pop()
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            reached.append(fn)
            for callee in self._local_callees(fn, defs):
                queue.append(callee)
        out: List[Finding] = []
        flagged: Set[int] = set()
        for fn in reached:
            for f in self._impure(ctx, fn, mutables):
                marker = (f.line, f.col, f.message)
                if marker not in flagged:
                    flagged.add(marker)
                    out.append(f)
        return out

    @staticmethod
    def _is_mutable_literal(value) -> bool:
        if isinstance(value, (ast.List, ast.Dict, ast.Set,
                              ast.ListComp, ast.DictComp, ast.SetComp)):
            return True
        if isinstance(value, ast.Call):
            return _dotted(value.func) in (
                "dict", "list", "set", "collections.deque",
                "collections.defaultdict", "collections.OrderedDict")
        return False

    def _roots(self, ctx: ModuleCtx,
               defs: Dict[str, ast.AST]) -> List[ast.AST]:
        roots: List[ast.AST] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    d = dec.func if isinstance(dec, ast.Call) else dec
                    if _dotted(d) in self._JIT_WRAP:
                        roots.append(node)
                    elif (isinstance(dec, ast.Call)
                          and _dotted(dec.func).endswith("partial")
                          and dec.args
                          and _dotted(dec.args[0]) in self._JIT_WRAP):
                        roots.append(node)
            elif isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                arg_idx = ()
                if dotted in self._JIT_WRAP:
                    arg_idx = (0,)
                elif dotted in self._TRACED_ARGS:
                    arg_idx = self._TRACED_ARGS[dotted]
                for i in arg_idx:
                    if i < len(node.args):
                        roots.extend(self._resolve(node.args[i], defs))
        return roots

    @staticmethod
    def _resolve(arg, defs: Dict[str, ast.AST]) -> List[ast.AST]:
        if isinstance(arg, ast.Lambda):
            return [arg]
        if isinstance(arg, ast.Name) and arg.id in defs:
            return [defs[arg.id]]
        if isinstance(arg, ast.Attribute) and arg.attr in defs:
            return [defs[arg.attr]]  # self._meth → any same-named def
        if isinstance(arg, ast.Call):
            # partial(f, ...) / jit(f) nests
            inner = [a for a in arg.args]
            out = []
            for a in inner:
                out.extend(JitPurityRule._resolve(a, defs))
            return out
        return []

    @staticmethod
    def _local_callees(fn, defs: Dict[str, ast.AST]) -> List[ast.AST]:
        out = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Name) \
                        and node.func.id in defs:
                    out.append(defs[node.func.id])
                elif (isinstance(node.func, ast.Attribute)
                      and isinstance(node.func.value, ast.Name)
                      and node.func.value.id == "self"
                      and node.func.attr in defs):
                    out.append(defs[node.func.attr])
        return out

    def _impure(self, ctx: ModuleCtx, fn,
                mutables: Set[str]) -> List[Finding]:
        out: List[Finding] = []
        local_shadow = {a.arg for a in getattr(fn, "args",
                                               ast.arguments(
                                                   posonlyargs=[],
                                                   args=[], kwonlyargs=[],
                                                   kw_defaults=[],
                                                   defaults=[])).args}
        for node in ast.walk(fn):
            dotted = _dotted(node) if isinstance(node,
                                                 ast.Attribute) else ""
            if dotted == "os.environ":
                out.append(self.finding(
                    ctx, node,
                    "os.environ read reachable from traced code — the "
                    "value freezes at compile time; hoist the "
                    "utils/knobs read out of the traced function"))
            elif isinstance(node, ast.Call):
                cd = _dotted(node.func)
                if cd == "os.getenv":
                    out.append(self.finding(
                        ctx, node,
                        "environment read reachable from traced code "
                        "— freezes at compile time"))
                elif cd in self._CLOCK_CALLS:
                    out.append(self.finding(
                        ctx, node,
                        "%s inside traced code measures trace time "
                        "once, not run time — time outside the jitted "
                        "program" % cd))
                elif cd.startswith("telemetry."):
                    out.append(self.finding(
                        ctx, node,
                        "telemetry call inside traced code records at "
                        "trace time only — record around the "
                        "dispatch, not inside it"))
                elif cd.startswith("metrics."):
                    out.append(self.finding(
                        ctx, node,
                        "metrics-registry call inside traced code "
                        "records at trace time only (and its knob "
                        "gate freezes) — mark around the dispatch, "
                        "not inside it"))
                elif cd.startswith("costmodel."):
                    out.append(self.finding(
                        ctx, node,
                        "cost-observatory call inside traced code "
                        "captures/tags at trace time only (and its "
                        "knob gate freezes) — wrap the dispatch "
                        "entry point, never the traced body"))
                elif cd.startswith("knobs."):
                    out.append(self.finding(
                        ctx, node,
                        "knob read inside traced code freezes the "
                        "env value at compile time — hoist the "
                        "%s call out of the traced function" % cd))
            elif (isinstance(node, ast.Name)
                  and isinstance(node.ctx, ast.Load)
                  and node.id in mutables
                  and node.id not in local_shadow):
                out.append(self.finding(
                    ctx, node,
                    "module-level mutable `%s` read inside traced "
                    "code — its trace-time contents are baked into "
                    "the compiled program" % node.id))
        return out


# ======================================================================
# R3 — knob registry
# ======================================================================
class KnobRegistryRule(Rule):
    """Every `GS_*` knob goes through utils/knobs.py: one typed
    declaration, live reads, KnobError on malformed values, and a
    README table rendered FROM the registry. Flags (a) any
    os.environ/os.getenv use in the package outside utils/knobs.py
    and the non-knob backend setup in core/platform.py, (b) `GS_*`
    string literals that aren't registered knobs (typo'd names read
    as silent defaults), (c) README knob-table drift from
    knobs.render_table()."""

    rule_id = "R3"
    name = "knob-registry"
    doc = ("GS_* env reads outside utils/knobs.py; unregistered GS_* "
           "literals; README knob-table drift")

    ALLOWED = (PKG + "/utils/knobs.py", PKG + "/core/platform.py")
    _GS_RE = re.compile(r"^GS_[A-Z0-9_]+$")

    @staticmethod
    def registry():
        """The live knob registry, loaded standalone by file path —
        importing the package itself would pull in jax and make the
        linter's verdict depend on the runtime environment. Cached in
        sys.modules (dataclasses resolves type hints through it)."""
        import sys

        if "_gs_knobs" in sys.modules:
            return sys.modules["_gs_knobs"]
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
            PKG, "utils", "knobs.py")
        spec = importlib.util.spec_from_file_location("_gs_knobs", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["_gs_knobs"] = mod
        spec.loader.exec_module(mod)
        return mod

    def check_module(self, ctx: ModuleCtx) -> List[Finding]:
        if not ctx.path.startswith(PKG + "/"):
            return []
        out: List[Finding] = []
        if ctx.path not in self.ALLOWED:
            for node in ast.walk(ctx.tree):
                if isinstance(node, ast.Attribute) \
                        and _dotted(node) == "os.environ":
                    out.append(self.finding(
                        ctx, node,
                        "os.environ access outside utils/knobs.py — "
                        "declare the knob in the registry and read it "
                        "with knobs.get_*"))
                elif isinstance(node, ast.Call) \
                        and _dotted(node.func) == "os.getenv":
                    out.append(self.finding(
                        ctx, node,
                        "os.getenv outside utils/knobs.py — declare "
                        "the knob in the registry"))
        known = set(self.registry().REGISTRY)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, str) \
                    and self._GS_RE.match(node.value) \
                    and node.value not in known:
                out.append(self.finding(
                    ctx, node,
                    "unregistered GS_* name %r — a typo'd knob reads "
                    "as its silent default; register it in "
                    "utils/knobs.py" % node.value))
        return out

    def check_project(self, ctxs: Sequence[ModuleCtx],
                      repo: str) -> List[Finding]:
        """README knob table == knobs.render_table(), row for row."""
        readme = os.path.join(repo, "README.md")
        if not os.path.exists(readme):
            return []
        with open(readme, encoding="utf-8") as f:
            text = f.read()
        knobs = self.registry()
        want = knobs.render_table()
        if want in text:
            return []
        want_rows = {line.split("|")[1].strip(): line
                     for line in want.splitlines()[2:]}
        have_rows = {}
        for line in text.splitlines():
            m = re.match(r"\|\s*(`GS_[A-Z0-9_]+`)\s*\|", line)
            if m:
                have_rows[m.group(1)] = line.strip()
        problems = []
        for name, row in want_rows.items():
            if name not in have_rows:
                problems.append("missing row %s" % name)
            elif have_rows[name] != row:
                problems.append("stale row %s" % name)
        for name in have_rows:
            if name not in want_rows:
                problems.append("unregistered row %s" % name)
        if not problems:
            problems = ["table block differs from render_table() "
                        "(row order or header)"]
        # stale/unregistered rows are the actionable ones; missing
        # rows are usually a wholesale-regeneration symptom — keep
        # the former ahead of the truncation cap
        problems.sort(key=lambda p: (p.startswith("missing"), p))
        return [Finding(
            rule=self.rule_id, name=self.name, path="README.md",
            line=1, col=0,
            message="README GS_* knob table drifted from the "
                    "utils/knobs registry: %s — regenerate with "
                    "`python -m tools.gslint --knob-table`"
                    % "; ".join(problems[:6]),
            symbol="<doc>", code="")]


# ======================================================================
# R4 — exception hygiene
# ======================================================================
class ExceptHygieneRule(Rule):
    """A broad except that swallows silently is how the resilience
    ladder loses evidence: ISSUE 2/6 built durable telemetry exactly
    so failures leave a ledger, and a bare `except Exception: pass`
    upstream of it deletes the ledger entry before it exists. Every
    broad/bare handler must re-raise (typed is better), record a
    flight-recorder event, or carry a pragma naming it a benign
    probe."""

    rule_id = "R4"
    name = "except-hygiene"
    doc = "broad/bare excepts that swallow errors silently"

    _RECORDERS = ("telemetry", "resilience", "faults")

    def check_module(self, ctx: ModuleCtx) -> List[Finding]:
        if not ctx.path.startswith(PKG + "/"):
            return []
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._is_broad(node.type):
                continue
            if self._compliant(node):
                continue
            out.append(self.finding(
                ctx, node,
                "broad except swallows errors silently — record a "
                "telemetry event, raise typed, or pragma "
                "`# gslint: disable=except-hygiene` for a genuinely "
                "benign probe"))
        return out

    @staticmethod
    def _is_broad(type_node) -> bool:
        if type_node is None:
            return True
        names = []
        if isinstance(type_node, ast.Tuple):
            names = [_dotted(e) for e in type_node.elts]
        else:
            names = [_dotted(type_node)]
        return any(n in ("Exception", "BaseException") for n in names)

    def _compliant(self, handler: ast.ExceptHandler) -> bool:
        for node in ast.walk(handler):
            if isinstance(node, ast.Raise):
                return True
            if isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                head = dotted.split(".")[0]
                if head in self._RECORDERS and "." in dotted:
                    return True
                if dotted.endswith("record_demotion"):
                    return True
        return False


# ======================================================================
# R5 — thread-shared state
# ======================================================================
class ThreadSharedRule(Rule):
    """The ingress pipeline runs prep on a worker pool while the main
    thread dispatches: module-level mutables in the modules those
    threads execute are shared state. Each one must either be
    accessed under a module-level Lock somewhere (the
    `with _X_LOCK:` discipline utils/resilience models) or carry a
    pragma declaring it thread-confined / benignly idempotent."""

    rule_id = "R5"
    name = "thread-shared"
    doc = ("module-level mutables in threaded modules without a "
           "lock-guarded access pattern")

    # modules executed by (or memoizing under) pipeline worker threads
    THREADED = (
        PKG + "/ops/ingress_pipeline.py",
        PKG + "/ops/autotune.py",
        PKG + "/ops/triangles.py",
        PKG + "/ops/windowed_reduce.py",
        PKG + "/ops/delta_egress.py",
        PKG + "/parallel/sharded.py",
        PKG + "/utils/telemetry.py",
        PKG + "/utils/metrics.py",
        PKG + "/utils/costmodel.py",
        PKG + "/utils/tracing.py",
        PKG + "/utils/resilience.py",
        PKG + "/utils/faults.py",
        PKG + "/utils/interning.py",
        # the serving front-end's connection/tail/pump threads and
        # the journal they append through (ISSUE 12)
        PKG + "/utils/wal.py",
        PKG + "/core/serve.py",
        # the admission sanitizer + dead-letter journal: serve
        # connection threads and the pump both reject (ISSUE 15)
        PKG + "/utils/sanitize.py",
        # the async pump: the dedicated pump thread runs cohort
        # dispatch (and the resident mailbox) concurrently with the
        # ingest-side connection/tail threads (ISSUE 18)
        PKG + "/core/tenancy.py",
        PKG + "/ops/resident_engine.py",
        PKG + "/utils/latency.py",
        PKG + "/ops/scan_analytics.py",
        # the provenance ledger: every finalize owner appends — serve
        # connection threads, the async pump, the driver (ISSUE 20)
        PKG + "/utils/provenance.py",
    )

    def check_module(self, ctx: ModuleCtx) -> List[Finding]:
        if ctx.path not in self.THREADED:
            return []
        mutables: Dict[str, ast.Assign] = {}
        locks: Set[str] = set()
        for node in ctx.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            dotted = _dotted(node.value.func) \
                if isinstance(node.value, ast.Call) else ""
            for t in node.targets:
                if not isinstance(t, ast.Name):
                    continue
                if dotted.endswith("Lock") or dotted.endswith("RLock"):
                    locks.add(t.id)
                elif JitPurityRule._is_mutable_literal(node.value):
                    mutables[t.id] = node
        guarded: Set[str] = set()
        written: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.With):
                ctx_names = {_dotted(item.context_expr).split(".")[0]
                             for item in node.items}
                if ctx_names & locks:
                    for inner in ast.walk(node):
                        if isinstance(inner, ast.Name) \
                                and inner.id in mutables:
                            guarded.add(inner.id)
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                written |= self._mutated_names(node, set(mutables))
        out: List[Finding] = []
        for name, node in sorted(mutables.items()):
            if name in guarded or name not in written:
                # never mutated from function scope = a read-only
                # table, not shared state
                continue
            out.append(self.finding(
                ctx, node,
                "module-level mutable `%s` in a threaded module is "
                "never accessed under a module Lock — guard it "
                "(`with <LOCK>:`) or pragma it thread-confined with "
                "the reason" % name))
        return out

    _MUTATORS = {"append", "add", "update", "setdefault", "pop",
                 "clear", "extend", "remove", "insert", "popleft",
                 "appendleft"}

    @classmethod
    def _mutated_names(cls, fn, candidates: Set[str]) -> Set[str]:
        """Names from `candidates` this function mutates: subscript/
        aug assignment, a mutating method call, or a `global` rebind."""
        out: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [getattr(node, "target", None)] \
                    if not isinstance(node, ast.Delete) else node.targets
                for t in targets:
                    if isinstance(t, ast.Subscript) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id in candidates:
                        out.add(t.value.id)
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id in candidates \
                    and node.func.attr in cls._MUTATORS:
                out.add(node.func.value.id)
            if isinstance(node, ast.Global):
                out |= set(node.names) & candidates
        return out


# ======================================================================
# R6 — checkpoint symmetry
# ======================================================================
class CheckpointSymmetryRule(Rule):
    """A key written by `state_dict` but never read by
    `load_state_dict` (or vice versa) is state that silently fails to
    survive a kill→resume — the exact failure class the ISSUE-2/6
    checkpoint ladder exists to prevent. Compared per class, only
    when BOTH methods are defined on the class (inherited halves are
    covered where they're defined)."""

    rule_id = "R6"
    name = "ckpt-symmetry"
    doc = "state_dict/load_state_dict key-set mismatches per class"

    def check_module(self, ctx: ModuleCtx) -> List[Finding]:
        if not ctx.path.startswith(PKG + "/"):
            return []
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            save = load = None
            for sub in node.body:
                if isinstance(sub, ast.FunctionDef):
                    if sub.name == "state_dict":
                        save = sub
                    elif sub.name == "load_state_dict":
                        load = sub
            if save is None or load is None:
                continue
            saved = self._saved_keys(save)
            loaded = self._loaded_keys(load)
            if not saved or not loaded:
                continue  # fully dynamic formats: nothing provable
            for key, knode in sorted(saved.items()):
                if key not in loaded:
                    out.append(self.finding(
                        ctx, knode,
                        "%s.state_dict writes key %r but "
                        "load_state_dict never reads it — dead state "
                        "or a missed restore" % (node.name, key)))
            for key, knode in sorted(loaded.items()):
                if key not in saved:
                    out.append(self.finding(
                        ctx, knode,
                        "%s.load_state_dict reads key %r that "
                        "state_dict never writes — a fresh checkpoint "
                        "cannot satisfy it" % (node.name, key)))
        return out

    @staticmethod
    def _saved_keys(fn: ast.FunctionDef) -> Dict[str, ast.AST]:
        """String keys the serializer produces: dict-literal keys and
        `X["k"] = ...` stores on locals (nested payload dicts under a
        single top-level key count too — load reads them through the
        same names)."""
        keys: Dict[str, ast.AST] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Dict):
                for k in node.keys:
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str):
                        keys.setdefault(k.value, k)
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets \
                    if isinstance(node, ast.Assign) else [node.target]
                for t in targets:
                    if isinstance(t, ast.Subscript) \
                            and isinstance(t.slice, ast.Constant) \
                            and isinstance(t.slice.value, str):
                        keys.setdefault(t.slice.value, t)
        return keys

    @staticmethod
    def _loaded_keys(fn: ast.FunctionDef) -> Dict[str, ast.AST]:
        """String keys the loader consumes: `state["k"]`,
        `state.get("k"[, d])`, `"k" in state`."""
        keys: Dict[str, ast.AST] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Subscript) \
                    and isinstance(node.ctx, ast.Load) \
                    and isinstance(node.slice, ast.Constant) \
                    and isinstance(node.slice.value, str):
                keys.setdefault(node.slice.value, node)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("get", "pop") \
                    and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                keys.setdefault(node.args[0].value, node.args[0])
            elif isinstance(node, ast.Compare) \
                    and len(node.ops) == 1 \
                    and isinstance(node.ops[0], (ast.In, ast.NotIn)) \
                    and isinstance(node.left, ast.Constant) \
                    and isinstance(node.left.value, str):
                keys.setdefault(node.left.value, node.left)
        return keys


def all_rules() -> List[Rule]:
    return [HostSyncRule(), JitPurityRule(), KnobRegistryRule(),
            ExceptHygieneRule(), ThreadSharedRule(),
            CheckpointSymmetryRule()]
