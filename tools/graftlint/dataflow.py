"""Per-function device-taint dataflow, shared across rules.

This is the forward taint pass GL003 pioneered, lifted out of the rule
so GL009 can reuse the SAME sink definitions: a device->host sync is a
hot-path stall for GL003 and a blocking call for GL009 (a fenced
transfer holds whatever lock the caller holds for the full device
round-trip).

``scan_scope`` walks ONE function scope (or the module top level) in
source order, tracking which locals are device-tainted (assigned from
``jnp.*``/``jax.*`` calls, from functions imported out of
``pilosa_tpu.ops.*``, from a ``jax.jit(...)`` alias, or from
expressions containing tainted names), and returns every sync sink it
sees plus the nested scopes with the taint they inherit. Callers
decide what a sink *means* (flag it, allow-list it, treat it as
blocking).
"""

from __future__ import annotations

import ast
from typing import List, Set, Tuple

from tools.graftlint.engine import SourceFile, dotted_name

SYNC_METHODS = {"item", "tolist", "block_until_ready"}
DEVICE_MODULE_PREFIXES = ("jnp.", "jax.")
OPS_MODULES = ("pilosa_tpu.ops.bitset", "pilosa_tpu.ops")
# ops.bitset exports that compute ON THE HOST (numpy in, numpy/int
# out): packing/unpacking, byte accounting, numpy mask builders. Their
# results carry no device taint — treating them as device producers
# made `pack_positions(...).tolist()` look like a fenced transfer.
HOST_OPS_FNS = frozenset({
    "range_mask_np", "pack_positions", "unpack_positions",
    "u64_to_words", "words_to_u64", "transfer_nbytes",
})

#: (sink Call node, human description) — what scan_scope yields.
Sink = Tuple[ast.AST, str]
#: (nested def/lambda node, taint inherited at its entry).
Nested = Tuple[ast.AST, Set[str]]


def imports_jax(sf: SourceFile) -> bool:
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Import):
            if any(a.name.split(".")[0] == "jax" for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "").split(".")[0] == "jax":
                return True
    return False


def imported_device_fns(sf: SourceFile) -> Set[str]:
    """Names imported from pilosa_tpu.ops.* — calls to these produce
    device arrays (b_and, popcount, ...)."""
    fns: Set[str] = set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.ImportFrom) \
                and node.module in OPS_MODULES:
            for a in node.names:
                if a.name.isupper():  # skip WORD_DTYPE-style consts
                    continue
                if a.name in HOST_OPS_FNS:  # host-side helpers
                    continue
                fns.add(a.asname or a.name)
    return fns


def is_host_materializer(value: ast.AST) -> bool:
    """Calls whose result lives on the host even when their input was a
    device array."""
    if not isinstance(value, ast.Call):
        return False
    fn = dotted_name(value.func)
    if fn in ("np.asarray", "np.array", "numpy.asarray",
              "numpy.array", "jax.device_get", "int", "float"):
        return True
    return isinstance(value.func, ast.Attribute) \
        and value.func.attr in ("item", "tolist")


def is_jit_alias(value: ast.AST) -> bool:
    return isinstance(value, ast.Call) \
        and dotted_name(value.func) in ("jax.jit", "jit", "jax.pmap")


def scan_scope(scope: ast.AST, inherited_taint: Set[str],
               device_fns: Set[str], *,
               proven_only: bool = False,
               ) -> Tuple[List[Sink], List[Nested]]:
    """One forward sweep over `scope`: returns (sync sinks, nested
    scopes). Nested defs/lambdas are NOT descended into — they run
    later, outside the lexical context being scanned; the caller
    recurses with the returned entry taint when that is what it
    models.

    ``proven_only=False`` (GL003's hot-path posture): ``.item()`` /
    ``.tolist()`` / ``np.asarray(attr)`` flag on ANY name/attribute
    receiver — in a file that imports jax, an untracked receiver is
    assumed device-resident. ``proven_only=True`` (GL009's posture):
    those sinks flag only on locals the taint pass PROVED device-
    resident — a numpy ``.tolist()`` is not a blocking hazard, and
    blocking-under-lock must not cry wolf on host marshalling."""
    taint = set(inherited_taint)
    jit_fns: Set[str] = set()
    sinks: List[Sink] = []
    nested_nodes: List[ast.AST] = []

    def is_device_call(call: ast.Call) -> bool:
        fn = dotted_name(call.func)
        if fn is None:
            return False
        if fn.startswith(DEVICE_MODULE_PREFIXES):
            # jnp.* / jax.* produce device values — except the host
            # fetcher, which is a sink, not a source.
            return fn != "jax.device_get"
        root = fn.split(".")[0]
        return root in device_fns or root in jit_fns

    def expr_tainted(e: ast.AST) -> bool:
        # Metadata access (x.shape / x.ndim / x.dtype / x.size) is
        # host-side and never syncs — skip those subtrees.
        stack = [e]
        while stack:
            n = stack.pop()
            if isinstance(n, ast.Attribute) \
                    and n.attr in ("shape", "ndim", "dtype", "size"):
                continue
            if isinstance(n, ast.Name) and n.id in taint:
                return True
            if isinstance(n, ast.Call) and is_device_call(n):
                return True
            stack.extend(ast.iter_child_nodes(n))
        return False

    for node in walk_scope(scope):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)) and node is not scope:
            nested_nodes.append(node)
            continue
        # -- taint propagation
        if isinstance(node, ast.Assign):
            if is_jit_alias(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jit_fns.add(t.id)
                continue
            if is_host_materializer(node.value):
                # np.asarray(device)/int(device)/x.tolist() RESULTS
                # are host values: the sink is collected below, but
                # the target must not stay device-tainted.
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        taint.discard(t.id)
            elif expr_tainted(node.value):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            taint.add(n.id)
        elif isinstance(node, ast.AugAssign):
            if expr_tainted(node.value) \
                    and isinstance(node.target, ast.Name):
                taint.add(node.target.id)
        elif isinstance(node, ast.For):
            if expr_tainted(node.iter):
                for n in ast.walk(node.target):
                    if isinstance(n, ast.Name):
                        taint.add(n.id)
        # -- sinks
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        fn = dotted_name(f)
        if isinstance(f, ast.Attribute) and f.attr in SYNC_METHODS:
            base = dotted_name(f.value)
            if f.attr == "block_until_ready" \
                    or expr_tainted(f.value) \
                    or (not proven_only
                        and isinstance(f.value, (ast.Attribute,
                                                 ast.Name))):
                sinks.append((node,
                              f"`{base or '<expr>'}.{f.attr}()` "
                              f"synchronizes device->host"))
        elif fn in ("jax.block_until_ready", "jax.device_get"):
            sinks.append((node, f"`{fn}` synchronizes device->host"))
        elif fn in ("np.asarray", "np.array", "numpy.asarray",
                    "numpy.array") and node.args:
            arg = node.args[0]
            if expr_tainted(arg) or (not proven_only
                                     and isinstance(arg, ast.Attribute)):
                sinks.append((node,
                              f"`{fn}(...)` fetches a device array to "
                              f"the host"))
        elif isinstance(f, ast.Name) and f.id in ("int", "float") \
                and node.args and expr_tainted(node.args[0]):
            sinks.append((node,
                          f"`{f.id}(...)` on a device value blocks on "
                          f"the transfer"))
    # Nested scopes inherit the END-of-scope taint: a closure sees the
    # final binding of every captured name, so a def that LEXICALLY
    # precedes `x = jnp.sum(bank)` still closes over the device value.
    nested: List[Nested] = [(n, set(taint)) for n in nested_nodes]
    return sinks, nested


def walk_scope(scope: ast.AST):
    """Yield nodes of one scope in SOURCE ORDER (the taint pass is a
    single forward sweep); nested function/lambda nodes are yielded (so
    the caller can recurse) but not descended into."""
    if isinstance(scope, ast.Lambda):
        roots = [scope.body]
    else:
        roots = list(scope.body)

    def rec(n):
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            for c in ast.iter_child_nodes(n):
                yield from rec(c)

    for r in roots:
        yield from rec(r)
