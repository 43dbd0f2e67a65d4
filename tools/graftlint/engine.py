"""graftlint core: file model, suppression comments, rule runner.

The linter is AST-based and project-aware: per-file rules receive a
parsed ``SourceFile``; cross-file rules (lock-order, retrace call
sites) receive the whole ``Project`` plus the shared semantic model
built by ``tools.graftlint.model``.

Suppression syntax (parsed from real comment tokens, so string
literals can't fake them):

- ``# graftlint: disable=GL001,GL003`` — suppress those rules on this
  line; when the comment is a standalone line it also covers the next
  line (for statements too long to carry a trailing comment).
- ``# graftlint: disable-file=GL004`` — suppress a rule for the whole
  file (used sparingly; prefer line-level with a justification).
- ``# graftlint: materialize`` — on (or directly above) a ``def`` /
  ``lambda`` line: marks the function as an explicit
  result-materialization point, exempt from GL003's host-sync rule.
  See docs/development.md for when this is acceptable.
- ``# graftlint: transient`` — on (or directly above) an assignment
  line: marks a device array stored on instance/module state as
  genuinely short-lived, exempt from GL007's ledger-coverage rule.
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

_DISABLE_RE = re.compile(
    r"#\s*graftlint:\s*disable=([A-Z0-9_,\s]+)")
_DISABLE_FILE_RE = re.compile(
    r"#\s*graftlint:\s*disable-file=([A-Z0-9_,\s]+)")
_MATERIALIZE_RE = re.compile(r"#\s*graftlint:\s*materialize\b")
_TRANSIENT_RE = re.compile(r"#\s*graftlint:\s*transient\b")


@dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    code: str
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.code} " \
               f"{self.message}"


@dataclass
class Config:
    """Rule scoping knobs. Defaults describe the real tree; tests
    override them to point rules at fixture files."""
    # GL003: packages whose functions must not host-sync unless
    # allow-listed as materialization points.
    hot_paths: Tuple[str, ...] = (
        "pilosa_tpu/ops/", "pilosa_tpu/executor/",
        "pilosa_tpu/storage/roaring.py")
    # GL005: files whose array dtypes are constrained to bitset words.
    word_dtype_paths: Tuple[str, ...] = ("pilosa_tpu/ops/bitset.py",)
    # GL001 (module-state sub-rule): packages where module-level mutable
    # state must be lock-guarded.
    state_paths: Tuple[str, ...] = (
        "pilosa_tpu/server/", "pilosa_tpu/parallel/", "pilosa_tpu/core/",
        "pilosa_tpu/pql/")
    # GL001 (factory sub-rule): package whose lock constructions must go
    # through pilosa_tpu.utils.locks.make_* (so PILOSA_TPU_LOCK_CHECK=1
    # instruments them); the factory module itself is exempt.
    factory_paths: Tuple[str, ...] = ("pilosa_tpu/",)
    factory_exempt: Tuple[str, ...] = ("pilosa_tpu/utils/locks.py",)
    # GL006: packages where every jax.jit/pmap build site must be
    # visible to the retrace counter (a _note_jit_compile call in an
    # enclosing function) — an untracked site is a blind spot for the
    # pilosa_executor_retrace series and /debug/queries.
    jit_tracked_paths: Tuple[str, ...] = ("pilosa_tpu/",)
    # GL007: packages where a device array stored on long-lived
    # instance/module state must reach LEDGER.register on every path
    # (so /debug/memory totals stay provable).
    ledger_paths: Tuple[str, ...] = ("pilosa_tpu/",)
    # GL008: packages where instance/module-level containers that grow
    # on request-driven paths must show eviction, a cap, or a ring
    # bound in scope.
    growth_paths: Tuple[str, ...] = ("pilosa_tpu/",)
    # GL009: packages where no blocking call (sleep, socket/HTTP,
    # thread join, subprocess, device sync) may run while a lock is
    # held — directly in the `with <lock>` body or in any function
    # transitively reachable from one.
    lock_block_paths: Tuple[str, ...] = ("pilosa_tpu/", "tools/")
    # GL010: packages where paired effects (register/unregister,
    # TIMELINE.begin/finish, inc/dec) opened and closed in the same
    # function must close on exception edges too.
    effect_paths: Tuple[str, ...] = ("pilosa_tpu/",)
    # GL011: packages where every foreign symbol called through a
    # ctypes library handle must have argtypes AND restype declared
    # (the native-boundary contract; pilosa_tpu/native.py _bind).
    ctypes_paths: Tuple[str, ...] = ("pilosa_tpu/", "tools/", "benches/")
    # GL012: packages where a function that hands a megakernel plan
    # buffer (an `.instrs` read) to the `_call_program` dispatch
    # funnel must reach ops/megakernel.verify_plan first — future IR
    # extensions cannot add an unverified launch path.
    plan_paths: Tuple[str, ...] = ("pilosa_tpu/",)
    # GL013: packages where FAILPOINTS.register sites live — each name
    # a string literal, registered exactly once, at module level (the
    # failpoint-catalog contract, pilosa_tpu/utils/failpoints.py).
    failpoint_paths: Tuple[str, ...] = ("pilosa_tpu/", "tools/",
                                        "benches/")
    # GL014: where the megakernel opcode table (OP_NAMES) and the
    # fuzzer coverage tables (OPCODE_MUTATIONS / PLAN_MUTATIONS) live.
    # Every opcode must map to at least one mutation kind the PV002
    # sweep applies — a new opcode cannot ship without fuzzer teeth.
    opcode_table_paths: Tuple[str, ...] = (
        "pilosa_tpu/ops/megakernel.py",)
    mutation_table_paths: Tuple[str, ...] = ("tools/planverify.py",)
    # GL015: packages where a guard read under one lock acquisition
    # must not control a dependent mutation under a LATER acquisition
    # of the same lock (directly or through a call that re-acquires) —
    # the resize-routing check-then-act shape.
    atomicity_paths: Tuple[str, ...] = ("pilosa_tpu/", "tools/")
    # GL016: packages where an attribute read under a class's lock
    # must be assigned under it too (outside __init__) — an
    # unsynchronized publication lets critical sections observe torn
    # state.
    publication_paths: Tuple[str, ...] = ("pilosa_tpu/", "tools/")
    select: Optional[Set[str]] = None
    ignore: Set[str] = field(default_factory=set)


class SourceFile:
    """One parsed python file plus its graftlint comment annotations."""

    def __init__(self, path: str, text: str):
        self.path = path.replace("\\", "/")
        self.text = text
        self.tree = ast.parse(text, filename=path)
        self.lines = text.splitlines()
        self.line_disables: Dict[int, Set[str]] = {}
        self.file_disables: Set[str] = set()
        self.materialize_lines: Set[int] = set()
        self.transient_lines: Set[int] = set()
        self._scan_comments()

    def _scan_comments(self) -> None:
        try:
            tokens = tokenize.generate_tokens(io.StringIO(self.text).readline)
            comments = [(t.start[0], t.string) for t in tokens
                        if t.type == tokenize.COMMENT]
        except tokenize.TokenError:
            comments = []
        for lineno, text in comments:
            standalone = self.lines[lineno - 1].lstrip().startswith("#") \
                if lineno - 1 < len(self.lines) else False
            targets = [lineno]
            if standalone:
                # A standalone comment (possibly the head of a comment
                # block) also covers the first code line that follows.
                ln = lineno + 1
                while ln <= len(self.lines) and (
                        not self.lines[ln - 1].strip()
                        or self.lines[ln - 1].lstrip().startswith("#")):
                    ln += 1
                targets.append(ln)
            m = _DISABLE_RE.search(text)
            if m:
                codes = {c.strip() for c in m.group(1).split(",")
                         if c.strip()}
                for ln in targets:
                    self.line_disables.setdefault(ln, set()).update(codes)
            m = _DISABLE_FILE_RE.search(text)
            if m:
                self.file_disables.update(
                    c.strip() for c in m.group(1).split(",") if c.strip())
            if _MATERIALIZE_RE.search(text):
                self.materialize_lines.update(targets)
            if _TRANSIENT_RE.search(text):
                self.transient_lines.update(targets)

    def suppressed(self, code: str, line: int) -> bool:
        if code in self.file_disables:
            return True
        return code in self.line_disables.get(line, set())

    def is_materialize(self, node: ast.AST) -> bool:
        """True when a def/lambda carries (or sits under) a
        ``# graftlint: materialize`` annotation. The annotation may be
        on the def line, the line above it, or above the first
        decorator."""
        lines = {node.lineno, node.lineno - 1}
        for deco in getattr(node, "decorator_list", []):
            lines.add(deco.lineno - 1)
        return bool(lines & self.materialize_lines)

    def is_transient(self, node: ast.AST) -> bool:
        """True when an assignment carries (or sits under) a
        ``# graftlint: transient`` annotation — on the statement line
        or the line above it."""
        return bool({node.lineno, node.lineno - 1} & self.transient_lines)

    def in_path(self, prefixes: Sequence[str]) -> bool:
        return any(p in self.path for p in prefixes)


class Project:
    """All files under lint, plus the lazily-built semantic model."""

    def __init__(self, files: List[SourceFile], config: Config):
        self.files = files
        self.config = config
        self._model = None
        self._callgraph = None

    @property
    def model(self):
        if self._model is None:
            from tools.graftlint.model import build_model
            self._model = build_model(self)
        return self._model

    @property
    def callgraph(self):
        """The interprocedural call graph, built ONCE per run and
        shared by every rule that follows calls (GL002 lock-order,
        GL006 note-reachability, GL007 ledger coverage, GL009
        blocking-under-lock)."""
        if self._callgraph is None:
            from tools.graftlint.callgraph import CallGraph
            self._callgraph = CallGraph(self.model)
        return self._callgraph


class Rule:
    """Base rule. Subclasses set `code`/`name` and override one of
    check_file (per-file) or check_project (cross-file)."""

    code = "GL000"
    name = "base"

    def check_file(self, sf: SourceFile,
                   project: Project) -> Iterable[Finding]:
        return ()

    def check_project(self, project: Project) -> Iterable[Finding]:
        return ()


def run_rules(project: Project,
              rules: Sequence[Rule]) -> List[Finding]:
    cfg = project.config
    active = [r for r in rules
              if (cfg.select is None or r.code in cfg.select)
              and r.code not in cfg.ignore]
    findings: List[Finding] = []
    by_path = {sf.path: sf for sf in project.files}
    for rule in active:
        for sf in project.files:
            findings.extend(rule.check_file(sf, project))
        findings.extend(rule.check_project(project))
    out = []
    for f in findings:
        sf = by_path.get(f.path)
        if sf is not None and sf.suppressed(f.code, f.line):
            continue
        out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    return out


# --------------------------------------------------------------- AST helpers

def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def walk_shallow(node: ast.AST, *, skip=(ast.FunctionDef,
                                         ast.AsyncFunctionDef,
                                         ast.Lambda)) -> Iterable[ast.AST]:
    """ast.walk that does not descend into nested function bodies —
    code in a nested def/lambda runs later, outside the lexical context
    (e.g. outside the lock region) being scanned."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, skip):
            stack.extend(ast.iter_child_nodes(n))
