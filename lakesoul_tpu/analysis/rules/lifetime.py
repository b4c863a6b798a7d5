"""Zero-copy buffer-lifetime rule for the PR-8 collate machinery.

The scan path's speed comes from *borrowing*: ``_np_column_views`` hands
out numpy views over Arrow batch buffers.  That is only sound inside a
window discipline — a view travels with the batch that owns its bytes.
Nothing type-checks that discipline, and a violation is not a crash but
silently corrupt training data.  One rule pins it:

- ``view-escapes-release``: the result of ``_np_column_views(batch)`` must
  stay inside the borrowing function's window: passing it as a call
  argument is the sanctioned hand-off, and storing a view together with
  its owning batch in one tuple is the rebatcher's keep-alive idiom
  (``self._pending.append((b, views))``).  Everything else escapes the
  release point: storing a bare view on ``self`` or into a container,
  returning it, or closing over it in a nested function — the borrower
  then outlives the batch that owns the bytes.
"""

from __future__ import annotations

import ast
from typing import Iterable

from lakesoul_tpu.analysis.engine import (
    Finding,
    Module,
    Rule,
    dotted_name,
    enclosing_function_bodies,
    walk_stopping_at_functions,
)

# the zero-copy loader module the rules default-scope to; fixtures override
SCOPE = ("data/jax_iter.py",)

_VIEW_FACTORY = "_np_column_views"

# container methods a borrowed value must not be handed into
_STORE_METHODS = {
    "append", "appendleft", "add", "insert", "extend", "update",
    "setdefault", "put", "put_nowait",
}


def _tracked_call(value: ast.expr) -> "tuple[str | None] | None":
    """``(source_name,)`` where the RHS is ``_np_column_views(x)``, else None.
    IfExp arms are checked too (``views = _np_column_views(b) if cap else
    None``)."""
    if isinstance(value, ast.IfExp):
        return _tracked_call(value.body) or _tracked_call(value.orelse)
    if not isinstance(value, ast.Call):
        return None
    name = dotted_name(value.func)
    terminal = (name or "").rsplit(".", 1)[-1]
    if terminal == _VIEW_FACTORY:
        src = value.args[0].id if (
            value.args and isinstance(value.args[0], ast.Name)
        ) else None
        return (src,)
    return None


class ViewEscapesReleaseRule(Rule):
    id = "view-escapes-release"
    title = "borrowed view escapes its release point"

    def __init__(self, scope: tuple = SCOPE):
        self.scope = scope

    def check(self, module: Module) -> Iterable[Finding]:
        if not any(s in module.relpath for s in self.scope):
            return
        for _, body in enclosing_function_bodies(module.tree):
            nodes = list(walk_stopping_at_functions(body))
            views: dict[str, str | None] = {}  # name -> owning-batch name
            for node in nodes:
                if isinstance(node, ast.Assign):
                    tracked = _tracked_call(node.value)
                    if tracked is None:
                        continue
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            views[t.id] = tracked[0]
            if views:
                yield from self._scan_escapes(module, nodes, views)

    # ------------------------------------------------------------- escapes
    def _borrowed(self, expr: ast.expr, views) -> "str | None":
        """The name of a borrowed view ``expr`` hands onward WITHOUT its
        keep-alive: a bare tracked name, or a tuple/list that contains a
        tracked view but NOT the batch that owns its bytes."""
        if isinstance(expr, ast.Name):
            return expr.id if expr.id in views else None
        if isinstance(expr, (ast.Tuple, ast.List)):
            names = {e.id for e in expr.elts if isinstance(e, ast.Name)}
            for n in names & set(views):
                src = views[n]
                if src is None or src not in names:
                    return n  # travelling without its batch
        return None

    def _scan_escapes(self, module, nodes, views) -> Iterable[Finding]:
        def finding(line: int, name: str, how: str) -> Finding:
            return Finding(
                self.id,
                module.relpath,
                line,
                f"view {name!r} {how} — it escapes the release point: the "
                "borrower can outlive the batch that owns its bytes (views "
                "must travel with their owning batch)",
            )

        for node in nodes:
            if isinstance(node, ast.Assign):
                if _tracked_call(node.value) is not None:
                    continue  # the tracking assignment itself
                name = self._borrowed(node.value, views)
                if name is not None and not isinstance(node.targets[0], ast.Name):
                    # a local rebind stays inside the window
                    yield finding(node.lineno, name, "is stored")
            elif isinstance(node, ast.Return) and node.value is not None:
                name = self._borrowed(node.value, views)
                if name is not None:
                    yield finding(node.lineno, name, "is returned")
            elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                if node.func.attr not in _STORE_METHODS:
                    continue
                for arg in node.args:
                    name = self._borrowed(arg, views)
                    if name is not None:
                        yield finding(node.lineno, name, f"is stored via .{node.func.attr}(...)")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                captured = {n.id for n in ast.walk(node) if isinstance(n, ast.Name)} & set(views)
                for name in sorted(captured):
                    yield finding(node.lineno, name, "is closed over")
