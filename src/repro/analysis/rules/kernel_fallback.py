"""kernel-fallback-shape (AIR006): every kernel package ships the chain.

The kernels grew a uniform shape over PRs 1–7: each
``repro/kernels/<name>/`` package exposes its public entry points from
``ops`` (re-exported by ``__init__``), keeps a pure-NumPy oracle in
``ref``, and — when it dispatches on a ``backend=`` argument — names the
full ``pallas → jnp → numpy`` fallback chain and imports jax *lazily*
(inside functions), so a CPU-only environment can still import and run
the numpy path.  A new kernel package that skips ``ref`` loses its
oracle tests; an eager module-level ``import jax`` in a dispatching
``ops`` breaks CPU-only import of the whole package.

Per scanned ``repro/kernels/<name>/`` package this rule checks:

* ``ops.py`` and ``ref.py`` exist,
* ``__init__.py`` imports from ``.ops``,
* if any function in ``ops.py`` takes a ``backend`` parameter: the
  module contains all three backend literals (``"pallas"``, ``"jnp"``,
  ``"numpy"``) and has no module-top-level ``import jax``.
"""
from __future__ import annotations

import ast
import os
import re

from ..core import Finding, ProjectRule, norm_path

_PKG_RE = re.compile(r"(?P<root>.*/repro/kernels)/(?P<pkg>[^/]+)/")

_BACKENDS = ("pallas", "jnp", "numpy")


def _parse(path):
    try:
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        return ast.parse(src, filename=path)
    except (SyntaxError, ValueError, OSError):
        return None  # AIR999 covers parse failures


def _has_backend_param(tree) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            a = node.args
            names = [p.arg for p in
                     a.posonlyargs + a.args + a.kwonlyargs]
            if "backend" in names:
                return True
    return False


def _string_literals(tree) -> set:
    return {n.value for n in ast.walk(tree)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


def _toplevel_jax_import(tree):
    """Module-level ``import jax`` / ``from jax... import`` node, if any.
    Imports inside functions (the lazy idiom) don't count."""
    for stmt in tree.body:
        if isinstance(stmt, ast.Import):
            if any(a.name == "jax" or a.name.startswith("jax.")
                   for a in stmt.names):
                return stmt
        elif isinstance(stmt, ast.ImportFrom):
            mod = stmt.module or ""
            if stmt.level == 0 and (mod == "jax"
                                    or mod.startswith("jax.")):
                return stmt
    return None


def _imports_from_ops(tree) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == "ops" or mod.endswith(".ops") \
                    or (node.level >= 1 and mod == "ops"):
                return True
            if any(a.name == "ops" for a in node.names):
                return True
    return False


class KernelFallbackShapeRule(ProjectRule):
    name = "kernel-fallback-shape"
    code = "AIR006"
    description = ("every repro/kernels/* package ships ops.py + ref.py, "
                   "re-exports from .ops, and a backend=-dispatching ops "
                   "names the pallas/jnp/numpy chain with lazy jax imports")

    def check_project(self, files):
        pkgs: dict[str, dict[str, str]] = {}
        for p in files:
            m = _PKG_RE.search(norm_path(p))
            if not m:
                continue
            pkgs.setdefault(m.group("pkg"), {})[
                os.path.basename(p)] = p
        for pkg in sorted(pkgs):
            members = pkgs[pkg]
            init = members.get("__init__.py")
            anchor = init or next(iter(sorted(members.values())))
            for required in ("ops.py", "ref.py"):
                if required not in members:
                    yield Finding(
                        rule=self.name, code=self.code, path=anchor,
                        line=1, col=1,
                        message=f"kernel package '{pkg}' is missing "
                                f"{required} — every kernel ships a "
                                f"dispatching ops module and a NumPy "
                                f"reference oracle")
            if init is not None:
                tree = _parse(init)
                if tree is not None and not _imports_from_ops(tree):
                    yield Finding(
                        rule=self.name, code=self.code, path=init,
                        line=1, col=1,
                        message=f"kernel package '{pkg}' __init__.py does "
                                f"not re-export from .ops")
            ops = members.get("ops.py")
            if ops is None:
                continue
            tree = _parse(ops)
            if tree is None:
                continue
            if not _has_backend_param(tree):
                continue  # no backend= dispatch, no chain to check
            literals = _string_literals(tree)
            missing = [b for b in _BACKENDS if b not in literals]
            if missing:
                yield Finding(
                    rule=self.name, code=self.code, path=ops, line=1,
                    col=1,
                    message=f"kernel package '{pkg}' ops.py dispatches on "
                            f"backend= but never names "
                            f"{', '.join(repr(b) for b in missing)} — the "
                            f"pallas → jnp → numpy chain must be complete")
            jax_imp = _toplevel_jax_import(tree)
            if jax_imp is not None:
                yield Finding(
                    rule=self.name, code=self.code, path=ops,
                    line=jax_imp.lineno, col=jax_imp.col_offset + 1,
                    message=f"kernel package '{pkg}' ops.py imports jax at "
                            f"module top level — backend-dispatching ops "
                            f"must import jax lazily so the numpy path "
                            f"works without jax")
