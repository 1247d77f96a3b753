"""PERF001-002: the devtime registry and the SLO catalog stay total.

The lfkt-perf contract (obs/devtime.py, obs/slo.py):

- PERF001 — every ``jax.jit``/``pjit``/``pl.pallas_call`` entry point in
  the package is registered with the devtime registry, so compile and
  dispatch attribution can never silently lose a program.  A site counts
  as registered when (a) the jit-creating call is lexically inside a
  ``timed_jit(...)``/``register_program(...)`` call (the wrap-at-build
  form: ``timed_jit("name", jax.jit(fn))``), or (b) the decorated
  function's name — or the enclosing function's name, for call-expression
  sites — appears as an argument (string or name) of a registration call
  somewhere in the same module (the module-level forms:
  ``prefill_jit = timed_jit("prefill", prefill_jit)`` after a decorated
  def, ``register_program("flash_attention", ...)`` for trace-inner
  dispatch sites whose compile wall belongs to their caller).
- PERF002 — every :class:`~..obs.slo.SLO` entry in ``obs/slo.py``
  references a metric family declared in the obs/catalog.py catalog
  (exactly, or via a ``prefix=True`` family): an SLO over a phantom
  family would evaluate forever-green burn rates against series that can
  never exist.

``obs/devtime.py`` itself is exempt from PERF001 (it creates no programs;
its fixtures of the wrapper would self-trigger on pathological parses).
"""

from __future__ import annotations

import ast

from .core import Context, Finding, const_str, dotted
from .jit import _decorator_is_jit
from .obsreg import _catalog, _covered

RULES = {
    "PERF001": "jax.jit/pallas_call entry point not registered with the "
               "devtime registry (obs/devtime.py)",
    "PERF002": "SLO references a metric family missing from the "
               "obs/catalog.py catalog",
}

SLO_REL = "obs/slo.py"
_EXEMPT = ("obs/devtime.py",)
_REG_FNS = ("timed_jit", "register_program")
_JIT_TAILS = ("jit", "pjit")


def _registration_info(tree: ast.AST) -> tuple[set[str], set[int]]:
    """(names registered in this module, ids of nodes lexically inside a
    registration call's arguments)."""
    names: set[str] = set()
    inside: set[int] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = dotted(node.func)
        if f is None or f.split(".")[-1] not in _REG_FNS:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            s = const_str(arg)
            if s:
                names.add(s)
            if isinstance(arg, ast.Name):
                names.add(arg.id)
            for sub in ast.walk(arg):
                inside.add(id(sub))
    return names, inside


def _enclosing_fn_map(tree: ast.AST) -> dict[int, str | None]:
    """node id -> name of the innermost enclosing function def (or None
    at module level)."""
    out: dict[int, str | None] = {}

    def assign(node: ast.AST, owner: str | None):
        for child in ast.iter_child_nodes(node):
            is_fn = isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef))
            out[id(child)] = owner
            assign(child, child.name if is_fn else owner)

    assign(tree, None)
    return out


def _decorator_nodes(tree: ast.AST) -> set[int]:
    """ids of every node inside a decorator expression (decorator-form jit
    sites are checked through their FunctionDef, not the call walk)."""
    out: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            for dec in node.decorator_list:
                for sub in ast.walk(dec):
                    out.add(id(sub))
    return out


def _is_jit_call(node: ast.Call) -> bool:
    f = dotted(node.func)
    if f is None:
        return False
    tail = f.split(".")[-1]
    if tail in _JIT_TAILS:
        return True
    if tail == "partial":
        # functools.partial(jax.jit, ...) — a jit factory being built
        for a in node.args:
            ad = dotted(a)
            if ad and ad.split(".")[-1] in _JIT_TAILS:
                return True
    return False


def check(ctx: Context) -> list[Finding]:
    out: list[Finding] = []

    # -- PERF001: every jit/pallas program is devtime-registered -----------
    for src in ctx.sources:
        if src.rel in _EXEMPT:
            continue
        path = ctx.display_path(src)
        registered, inside_reg = _registration_info(src.tree)
        enclosing = _enclosing_fn_map(src.tree)
        in_decorator = _decorator_nodes(src.tree)

        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if not any(_decorator_is_jit(d) for d in node.decorator_list):
                    continue
                if node.name in registered:
                    continue
                out.append(Finding(
                    "PERF001", path, node.lineno,
                    f"jit-decorated {node.name} is not registered with the "
                    "devtime registry: wrap it (name = timed_jit(...)) or "
                    "declare it (register_program(...)) so compile/dispatch "
                    "attribution cannot lose it (obs/devtime.py)"))
                continue
            if not isinstance(node, ast.Call) or id(node) in in_decorator:
                continue
            f = dotted(node.func)
            tail = f.split(".")[-1] if f else None
            if tail == "pallas_call" or _is_jit_call(node):
                if id(node) in inside_reg:
                    continue
                owner = enclosing.get(id(node))
                if owner is not None and owner in registered:
                    continue
                kind = "pallas_call" if tail == "pallas_call" else "jax.jit"
                where = f"inside {owner}" if owner else "at module level"
                out.append(Finding(
                    "PERF001", path, node.lineno,
                    f"{kind} {where} is not registered with the devtime "
                    "registry: wrap the built callable in timed_jit(...) "
                    "or register_program() the enclosing function "
                    "(obs/devtime.py)"))

    # -- PERF002: SLO -> catalog coverage ----------------------------------
    metrics, have_catalog = _catalog(ctx)
    if not have_catalog:
        return out
    for src in ctx.sources:
        if src.rel != SLO_REL:
            continue
        path = ctx.display_path(src)
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            f = dotted(node.func)
            if f is None or f.split(".")[-1] != "SLO":
                continue
            metric = None
            for kw in node.keywords:
                if kw.arg == "metric":
                    metric = const_str(kw.value)
            if metric is None and len(node.args) > 1:
                metric = const_str(node.args[1])
            if metric is None:
                continue                    # dynamic: runtime lookup guards
            if not _covered(metric, metrics):
                out.append(Finding(
                    "PERF002", path, node.lineno,
                    f"SLO references metric {metric!r}, which is not in "
                    "the obs/catalog.py catalog — its burn rate would "
                    "evaluate forever-green against series that cannot "
                    "exist"))
    return out
