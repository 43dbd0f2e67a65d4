"""GL003 — host-device sync in the hot path.

In the configured hot-path files (ops/, executor/, storage/roaring.py)
every device->host materialization must happen at an explicitly
allow-listed boundary. The paper-side invariant: bitmap loops stay on
device as packed-word XLA ops; a stray ``.item()`` or
``np.asarray`` mid-pipeline serializes the dispatch queue and drags a
128 KiB shard row through the host per call.

Flagged constructs inside non-allow-listed functions:

- ``x.item()``, ``x.tolist()`` on anything;
- ``jax.block_until_ready`` / ``x.block_until_ready()``;
- ``jax.device_get``;
- ``np.asarray(x)`` / ``np.array(x)`` where ``x`` is a *device-tainted*
  local, a direct ``jnp.*``/device-kernel call, or an attribute access
  (attributes like ``result.words`` hold device arrays; host-marshalling
  of attribute lists needs a one-line justification disable);
- ``int(x)`` / ``float(x)`` where ``x`` is device-tainted.

The taint dataflow and sink definitions live in
``tools.graftlint.dataflow`` (shared with GL009, which treats the same
sinks as blocking calls when they run under a lock). Nested
defs/lambdas inherit the enclosing taint (closures).

Allow-listing:
- ``# graftlint: materialize`` on the def (see engine docstring);
- any lambda or local function passed as the first argument to
  ``_Pending(...)`` — pending-result finalizers ARE the design's
  materialization boundary (executor/executor.py);
- files that never import jax/jnp or the ops kernels are skipped
  (pure-host modules like storage/roaring.py stay cheap to lint).
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Set

from tools.graftlint.dataflow import (
    imported_device_fns, imports_jax, scan_scope,
)
from tools.graftlint.engine import (
    Finding, Project, Rule, SourceFile, dotted_name,
)


class GL003HostSync(Rule):
    code = "GL003"
    name = "host-sync-in-hot-path"

    def check_file(self, sf: SourceFile,
                   project: Project) -> Iterable[Finding]:
        if not sf.in_path(project.config.hot_paths):
            return []
        device_fns = imported_device_fns(sf)
        if not device_fns and not imports_jax(sf):
            return []  # pure-host module: no device values can exist
        out: List[Finding] = []
        pending_ok = self._pending_finalizers(sf)
        self._check_scope(sf, sf.tree, set(), device_fns, pending_ok, out,
                          allowed=False)
        return out

    @staticmethod
    def _pending_finalizers(sf: SourceFile) -> Set[int]:
        """id()s of lambda/function-name nodes passed as the first arg
        to _Pending(...) — implicit materialization points."""
        ok: Set[int] = set()
        names: Set[str] = set()
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call) \
                    and dotted_name(node.func) in ("_Pending", "Pending") \
                    and node.args:
                first = node.args[0]
                if isinstance(first, ast.Lambda):
                    ok.add(id(first))
                elif isinstance(first, ast.Name):
                    names.add(first.id)
        if names:
            for node in ast.walk(sf.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and node.name in names:
                    ok.add(id(node))
        return ok

    def _check_scope(self, sf: SourceFile, scope: ast.AST,
                     inherited_taint: Set[str], device_fns: Set[str],
                     pending_ok: Set[int], out: List[Finding],
                     allowed: bool) -> None:
        """Scan one scope with the shared dataflow, flag its sinks
        unless `allowed`, recurse into nested scopes with the
        accumulated taint (function params are host values by default;
        closures keep the enclosing taint)."""
        sinks, nested = scan_scope(scope, inherited_taint, device_fns)
        if not allowed:
            for node, what in sinks:
                self._flag(sf, node, out, what)
        for sub, taint in nested:
            sub_allowed = allowed or id(sub) in pending_ok \
                or sf.is_materialize(sub)
            self._check_scope(sf, sub, taint, device_fns, pending_ok,
                              out, sub_allowed)

    def _flag(self, sf: SourceFile, node: ast.AST, out: List[Finding],
              what: str) -> None:
        out.append(Finding(
            sf.path, node.lineno, node.col_offset, self.code,
            f"{what} inside a hot-path function — move it behind a "
            f"`# graftlint: materialize` boundary or justify with a "
            f"disable comment"))
