"""KTL001 — donation aliasing.

Historical bugs pinned: PR 6 (checkpoint restore leaves zero-copied from
aligned host arrays, donated on the first step, heap recycled under live
weights) and PR 8 (``jnp.asarray`` borrowing the numpy ``self._bt_host``
/ ``self._pos_host`` mirrors while the donated cache let XLA alias
segment outputs onto them). Canonical fix: ``serving/model_runner.py``
``_upload_mirror`` — ``jnp.asarray(arr) + 0`` forces an XLA-owned buffer.

What makes a borrow dangerous is *persistence*: ``jnp.asarray`` of a
local list copies, and a borrow of a transient array nobody mutates is
harmless. The rule therefore flags, per file (given at least one
``jit(..., donate_argnums=...)``):

1. a borrow of a **self attribute** (``jnp.asarray(self._bt_host)`` /
   ``np.frombuffer(self._buf)`` — a host mirror that outlives the call)
   passed at ANY argument of a donated call without a defensive copy
   (``+ 0``, ``jnp.copy``, ``np.array``);
2. ANY borrow passed at a **donated position** (donation frees XLA to
   recycle the borrowed numpy heap under live data — the PR 6 restore
   shape);
3. ANY borrow stored into a **donated-cache attribute** (an attribute
   that is itself passed at a donated position somewhere in the file),
   or into an item of one (``self.cache["bt"] = jnp.asarray(bt)``: the
   runner's shape, where the mirror arrives as an argument).

Taint propagates through simple local assignment and is cleared by the
defensive copies above.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

RULE_ID = "KTL001"

_BORROW_FUNCS = {"asarray", "frombuffer"}
_COPY_FUNCS = {"copy", "array", "deepcopy"}

#: taint levels
_BORROW = 1          # borrow of a transient value
_MIRROR_BORROW = 2   # borrow of a persistent self attribute


def _call_attr(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _is_self_attr(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    )


def _attr_key(node: ast.AST) -> Optional[str]:
    if _is_self_attr(node):
        return node.attr
    return None


def _is_jit_call(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and _call_attr(node) == "jit"
        and any(kw.arg == "donate_argnums" for kw in node.keywords)
    )


def _donated_positions(node: ast.Call) -> Optional[Set[int]]:
    """Parse donate_argnums=(1,) -> {1}; None when not statically known."""
    for kw in node.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return {v.value}
        if isinstance(v, (ast.Tuple, ast.List)):
            out = set()
            for elt in v.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                    out.add(elt.value)
                else:
                    return None
            return out
    return None


def _callee_key(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
            and f.value.id == "self":
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


class _DonationIndex(ast.NodeVisitor):
    """Pass 1: donated callables (name -> donated positions, None=any)
    plus donated-attribute names (attrs passed at donated positions)."""

    def __init__(self) -> None:
        self.donated_fns: Dict[str, Optional[Set[int]]] = {}
        self.donated_attrs: Set[str] = set()
        self._calls: List[ast.Call] = []

    def visit_Assign(self, node: ast.Assign) -> None:
        if _is_jit_call(node.value):
            pos = _donated_positions(node.value)
            for t in node.targets:
                key = _attr_key(t) or (t.id if isinstance(t, ast.Name) else None)
                if key:
                    self.donated_fns[key] = pos
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        self._calls.append(node)
        self.generic_visit(node)

    def _positions_for(self, call: ast.Call) -> Optional[Set[int]]:
        """Donated positions for a call, or None if the call isn't donated
        (note: a donated call with unparseable argnums returns set())."""
        key = _callee_key(call)
        if key is not None and key in self.donated_fns:
            return self.donated_fns[key] or set()
        if _is_jit_call(call.func):
            return _donated_positions(call.func) or set()
        return None

    def finish(self) -> None:
        for call in self._calls:
            pos = self._positions_for(call)
            if pos is None:
                continue
            for i, arg in enumerate(call.args):
                if i in pos:
                    attr = _attr_key(arg)
                    if attr:
                        self.donated_attrs.add(attr)


class _TaintChecker(ast.NodeVisitor):
    """Pass 2: per-function borrow-taint propagation + flagging."""

    def __init__(self, ctx, index: _DonationIndex) -> None:
        self.ctx = ctx
        self.index = index
        self.findings: List = []
        self._tainted: List[Dict[str, int]] = [{}]

    def visit_FunctionDef(self, node) -> None:
        self._tainted.append({})
        self.generic_visit(node)
        self._tainted.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _taint_of(self, node: ast.AST) -> int:
        """0 = clean, _BORROW, or _MIRROR_BORROW."""
        if isinstance(node, ast.Call) and _call_attr(node) in _BORROW_FUNCS:
            if node.args and (
                _is_self_attr(node.args[0])
                or self._taint_of(node.args[0]) >= _MIRROR_BORROW
            ):
                return _MIRROR_BORROW
            return _BORROW
        if isinstance(node, ast.Name):
            return self._tainted[-1].get(node.id, 0)
        return 0

    def _is_defensive(self, node: ast.AST) -> bool:
        if isinstance(node, ast.BinOp):
            return True  # asarray(x) + 0 and friends materialize
        if isinstance(node, ast.Call) and _call_attr(node) in _COPY_FUNCS:
            return True
        return False

    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)
        value = node.value
        taint = 0 if self._is_defensive(value) else self._taint_of(value)
        for t in node.targets:
            if isinstance(t, ast.Name):
                if taint:
                    self._tainted[-1][t.id] = taint
                else:
                    self._tainted[-1].pop(t.id, None)
            else:
                # ``self.X[...] = borrow`` lands in self.X as ``self.X = `` does
                attr = _attr_key(t.value if isinstance(t, ast.Subscript) else t)
                if attr and taint and attr in self.index.donated_attrs:
                    self.findings.append(self.ctx.finding(
                        RULE_ID, node,
                        f"borrowed buffer stored into donated attribute "
                        f"self.{attr} without a defensive copy "
                        f"(jnp.copy / np.array / `+ 0`): donation lets XLA "
                        f"recycle the borrowed host memory under live data",
                    ))

    def visit_Call(self, node: ast.Call) -> None:
        self.generic_visit(node)
        pos = self.index._positions_for(node)
        if pos is None:
            return
        key = _callee_key(node) or "jit(...)"
        for i, arg in enumerate(node.args):
            if self._is_defensive(arg):
                continue
            taint = self._taint_of(arg)
            if taint >= _MIRROR_BORROW:
                self.findings.append(self.ctx.finding(
                    RULE_ID, arg,
                    f"borrow of a persistent host mirror "
                    f"(jnp.asarray/np.frombuffer of a self attribute) "
                    f"passed to donated call {key}() at arg {i} without a "
                    f"defensive copy — the PR 8 aliasing bug shape "
                    f"(see serving/model_runner.py _upload_mirror)",
                ))
            elif taint and i in pos:
                self.findings.append(self.ctx.finding(
                    RULE_ID, arg,
                    f"borrowed buffer donated at arg {i} of {key}() "
                    f"without a defensive copy — donation recycles the "
                    f"borrowed numpy heap (the PR 6 restore bug shape)",
                ))


def check_file(ctx) -> List:
    index = _DonationIndex()
    index.visit(ctx.tree)
    index.finish()
    if not index.donated_fns:
        return []
    checker = _TaintChecker(ctx, index)
    checker.visit(ctx.tree)
    return checker.findings
