"""GL005 — bitset word dtype invariant.

In the word-kernel file (ops/bitset.py) every array creation and cast
must stay on the packed-word dtype lattice:

- allowed: uint8/uint16/uint32/uint64 (words and sub-word views),
  int32 (popcount accumulators — the TPU VPU's native reduce dtype),
  bool/bool_ (predicate masks).
- flagged: int64 (silently truncated to i32 when jax_enable_x64 is
  off — exactly the class of bug that corrupts high word indices),
  int8/int16, every float/complex dtype (a float round-trip destroys
  bit patterns), and array *creation* with no explicit dtype (jnp
  defaults to float32/weak int — never what a word kernel wants).

Checked constructs: ``x.astype(D)``, ``dtype=D`` keywords, scalar-cast
calls ``jnp.int64(x)`` / ``np.float32(x)``, and dtype-less
``jnp.zeros/ones/full/empty/array/asarray`` creations.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional

from tools.graftlint.engine import (
    Finding, Project, Rule, SourceFile, dotted_name,
)

_ALLOWED = {"uint8", "uint16", "uint32", "uint64", "int32", "bool_",
            "bool"}
_BAD = {"int64", "int16", "int8", "float16", "float32", "float64",
        "bfloat16", "complex64", "complex128", "int_", "float_",
        "double", "single", "longlong"}
_CREATORS = {"zeros", "ones", "full", "empty", "array", "asarray"}
_ARRAY_MODULES = ("jnp", "np", "numpy", "jax.numpy")


def _dtype_name(node: ast.AST) -> Optional[str]:
    """Terminal dtype name for `np.uint32` / `jnp.int64` / `"uint32"` /
    bare `int`/`float`; None when unrecognizable (left alone)."""
    d = dotted_name(node)
    if d is not None:
        parts = d.split(".")
        if len(parts) >= 2 and parts[0] in ("np", "numpy", "jnp", "jax"):
            return parts[-1]
        if len(parts) == 1 and parts[0] in ("int", "float", "bool"):
            return {"int": "int64", "float": "float64",
                    "bool": "bool"}[parts[0]]
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value.lstrip("<>=")
    return None


class GL005DtypeInvariant(Rule):
    code = "GL005"
    name = "dtype-invariant"

    def check_file(self, sf: SourceFile,
                   project: Project) -> Iterable[Finding]:
        if not sf.in_path(project.config.word_dtype_paths):
            return []
        out: List[Finding] = []
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = dotted_name(node.func)
            # x.astype(D)
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "astype" and node.args:
                self._check_dtype_expr(sf, node.args[0], "astype", out)
                continue
            # scalar casts jnp.int64(x) etc.
            if fn is not None:
                parts = fn.split(".")
                if len(parts) == 2 and parts[0] in ("np", "jnp", "numpy"):
                    name = parts[1]
                    if name in _BAD:
                        out.append(self._finding(
                            sf, node, f"scalar cast `{fn}(...)`"))
                    elif name in _CREATORS:
                        self._check_creator(sf, node, fn, out)
            # dtype= keyword on any other call (jax.ShapeDtypeStruct,
            # jnp.sum(dtype=...), ...)
            for kw in node.keywords:
                if kw.arg == "dtype":
                    self._check_dtype_expr(sf, kw.value, fn or "call",
                                           out)
        return out

    # Positional index of the dtype parameter per creator (`full` takes
    # a fill value before it).
    _DTYPE_POS = {"zeros": 1, "ones": 1, "empty": 1, "array": 1,
                  "asarray": 1, "full": 2}

    def _check_creator(self, sf: SourceFile, node: ast.Call, fn: str,
                       out: List[Finding]) -> None:
        if any(kw.arg == "dtype" for kw in node.keywords):
            return  # dtype= kwarg is checked by the caller's kw loop
        pos = self._DTYPE_POS[fn.split(".")[-1]]
        if len(node.args) > pos:
            # Positional dtype present: check it when recognizable and
            # leave non-literal expressions alone — exactly like an
            # unrecognized `dtype=` expression.
            self._check_dtype_expr(sf, node.args[pos], fn, out)
            return
        out.append(self._finding(
            sf, node, f"`{fn}(...)` with no explicit dtype (defaults "
            f"to float/weak-int)"))

    def _check_dtype_expr(self, sf: SourceFile, expr: ast.AST,
                          ctx: str, out: List[Finding]) -> None:
        name = _dtype_name(expr)
        if name is None:
            return
        if name in _BAD or name not in _ALLOWED:
            out.append(self._finding(
                sf, expr, f"dtype `{name}` in `{ctx}`"))

    def _finding(self, sf: SourceFile, node: ast.AST,
                 what: str) -> Finding:
        return Finding(
            sf.path, node.lineno, node.col_offset, self.code,
            f"{what}: bitset word kernels must stay on "
            f"uint32/uint64 (int32 accumulators, bool masks) — "
            f"int64/float promotion silently corrupts packed words")
