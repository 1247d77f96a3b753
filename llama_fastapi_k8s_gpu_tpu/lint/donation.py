"""DON001-002: donated-buffer safety at the CALL sites.

Every hot-path jit in this codebase donates its state (``donate_argnames``
on the prefill/decode/page-copy programs): XLA reuses the argument's HBM
for the result, so the caller's reference is dead the moment the call is
dispatched.  The engines' contract is rebind-from-result
(``self._bstate, toks = chunk_jit(..., self._bstate, ...)``) or
drop-the-ref-across-the-call (the PR-6 restore hardening).  Nothing
checked that contract statically — a stale alias serves garbage (or
crashes with a donated-buffer error) at first request, not at review.

- **DON001** — the caller reads the donated local/attribute after the
  dispatch without rebinding it first (``f(self._cache)`` then
  ``self._cache["k"]``).
- **DON002** — an *alias* of the donated value survives the dispatch: a
  name assigned from it before the call and read after, or a
  ``self.<attr>`` stash of the value still live at function exit
  (``self._snap = cache; f(cache)`` — ``self._snap`` now names a dead
  buffer for whoever runs next).

The donor registry is built from the same surface PERF001 enumerates:
``jax.jit``/``functools.partial(jax.jit, ...)`` entry points with
``donate_argnames`` (decorator, assignment, and ``timed_jit``-wrapped
forms), jit *factories* (a function returning a donating jit over a
nested def; the package has none since PR 60), plus one level of
interprocedural propagation: a function that forwards its own parameter
into a donated position donates that parameter too (``KVPool.restore``'s
``ring``, ``Engine._prefill_padded``'s ``cache``).

Scope: intraprocedural per caller, names and ``self.<attr>`` keys only;
attribute writes by callees are invisible.  Deliberately donation-only —
plain aliasing is fine, it is aliasing ACROSS a donating dispatch that
the runtime forbids.
"""

from __future__ import annotations

import ast

from .cfg import build_cfg, eval_roots, solve_forward
from .core import Context, Finding, Source, const_str, dotted, str_seq

RULES = {
    "DON001": "donated argument is read after the donating dispatch "
              "without being rebound (use-after-donate)",
    "DON002": "an alias of a donated value survives the dispatch (stale "
              "reference to a dead buffer)",
}

_JIT_TAILS = ("jit", "pjit")


class _Donor:
    __slots__ = ("params", "donated", "method")

    def __init__(self, params: list[str], donated: list[str], method: bool):
        self.params = params
        self.donated = [d for d in donated if d in params]
        self.method = method


def _donate_kw(call: ast.Call) -> list[str] | None:
    for kw in call.keywords:
        if kw.arg in ("donate_argnames", "donate_argnums"):
            if kw.arg == "donate_argnums":
                return None     # index form unused in-tree; skip safely
            seq = str_seq(kw.value)
            if seq is not None:
                return seq
            one = const_str(kw.value)
            if one is not None:
                return [one]
    return None


def _is_jit_call(call: ast.Call) -> bool:
    f = dotted(call.func)
    return bool(f) and f.split(".")[-1] in _JIT_TAILS


def _jit_donation(call: ast.Call) -> list[str] | None:
    """Donated names when ``call`` builds a donating jit: ``jax.jit(...,
    donate_argnames=...)`` or ``partial(jax.jit, donate_argnames=...)``."""
    f = dotted(call.func)
    tail = f.split(".")[-1] if f else None
    if tail in _JIT_TAILS:
        return _donate_kw(call)
    if tail == "partial" and any(
            (d := dotted(a)) and d.split(".")[-1] in _JIT_TAILS
            for a in call.args):
        return _donate_kw(call)
    return None


def _params_of(fn) -> list[str]:
    a = fn.args
    return [p.arg for p in list(a.posonlyargs) + list(a.args)]


def _parent_class_map(tree: ast.AST) -> dict[int, bool]:
    """id(FunctionDef) -> is a method (direct child of a ClassDef)."""
    out: dict[int, bool] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            for child in node.body:
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out[id(child)] = True
    return out


def _unwrap_timed(call: ast.Call) -> ast.AST:
    """``timed_jit("name", X, ...)`` -> X (the wrapped callable expr)."""
    f = dotted(call.func)
    if f and f.split(".")[-1] == "timed_jit" and len(call.args) >= 2:
        return call.args[1]
    return call


def build_registry(ctx: Context) -> tuple[dict, dict]:
    """(donors, factories): donors maps a callable name (def name, assign
    target, or propagated function/method name) -> _Donor; factories maps
    a factory function name -> the inner def's _Donor (for ``F(...)(...)``
    call-of-call sites)."""
    donors: dict[str, _Donor] = {}
    factories: dict[str, _Donor] = {}
    fns_by_name: list[tuple[Source, object, bool]] = []

    for src in ctx.sources:
        methods = _parent_class_map(src.tree)
        local_defs = {n.name: n for n in ast.walk(src.tree)
                      if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fns_by_name.append((src, node, id(node) in methods))
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call):
                        don = _jit_donation(dec)
                        if don:
                            donors[node.name] = _Donor(
                                _params_of(node), don, id(node) in methods)
                # factory: returns a (possibly timed_jit-wrapped) donating
                # jit over a nested def
                for sub in ast.walk(node):
                    if not isinstance(sub, ast.Return) or sub.value is None:
                        continue
                    expr = sub.value
                    if isinstance(expr, ast.Call):
                        expr = _unwrap_timed(expr)
                    if isinstance(expr, ast.Call):
                        don = _jit_donation(expr)
                        inner = expr.args[0] if expr.args else None
                        if don and isinstance(inner, ast.Name):
                            target = local_defs.get(inner.id)
                            if target is not None:
                                factories[node.name] = _Donor(
                                    _params_of(target), don, False)
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call):
                expr = _unwrap_timed(node.value)
                don = None
                params: list[str] | None = None
                if isinstance(expr, ast.Call):
                    don = _jit_donation(expr)
                    inner = expr.args[0] if expr.args else None
                    if don is None and isinstance(expr.func, ast.Call):
                        # partial(jax.jit, donate_argnames=...)(fnref)
                        don = _jit_donation(expr.func)
                        inner = expr.args[0] if expr.args else None
                    if don and isinstance(inner, ast.Name):
                        target = local_defs.get(inner.id)
                        if target is not None:
                            params = _params_of(target)
                if don and params is not None:
                    donors[node.targets[0].id] = _Donor(params, don, False)
                elif isinstance(node.value, ast.Call):
                    # name-preserving rewrap: X = timed_jit("n", X) keeps
                    # X's existing registration — nothing to do
                    pass

    # one-level-per-round propagation to fixpoint: F donates parameter p
    # when F's body forwards p into a donated position of a known donor
    for _ in range(6):
        changed = False
        for src, fn, is_method in fns_by_name:
            params = _params_of(fn)
            pool = set(params[1:] if is_method else params)
            found: list[str] = []
            for call in ast.walk(fn):
                if not isinstance(call, ast.Call):
                    continue
                for arg_expr, _pname in donated_args(call, donors, factories):
                    if isinstance(arg_expr, ast.Name) \
                            and arg_expr.id in pool:
                        found.append(arg_expr.id)
            if found:
                cur = donors.get(fn.name)
                new = sorted(set(found) | set(cur.donated if cur else ()))
                if cur is None or set(new) != set(cur.donated):
                    donors[fn.name] = _Donor(params, new, is_method)
                    changed = True
        if not changed:
            break
    return donors, factories


def donated_args(call: ast.Call, donors: dict, factories: dict
                 ) -> list[tuple[ast.AST, str]]:
    """(argument expression, donated param name) pairs for this call."""
    donor = None
    method_call = False
    f = dotted(call.func)
    if f is not None:
        donor = donors.get(f.split(".")[-1])
        method_call = isinstance(call.func, ast.Attribute)
    elif isinstance(call.func, ast.Call):
        inner = dotted(call.func.func)
        if inner is not None:
            donor = factories.get(inner.split(".")[-1])
    if donor is None:
        return []
    params = donor.params
    if donor.method and method_call:
        params = params[1:]         # bound call: self is implicit
    out: list[tuple[ast.AST, str]] = []
    for name in donor.donated:
        if name not in params:
            continue
        idx = params.index(name)
        if idx < len(call.args):
            out.append((call.args[idx], name))
            continue
        for kw in call.keywords:
            if kw.arg == name:
                out.append((kw.value, name))
    return out


# ---------------------------------------------------------------------------
# per-caller dataflow
# ---------------------------------------------------------------------------

def _key_of(expr: ast.AST) -> str | None:
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name) \
            and expr.value.id == "self":
        return "self." + expr.attr
    return None


def _loads(stmt: ast.stmt) -> set[str]:
    out: set[str] = set()
    for root in eval_roots(stmt):
        for sub in ast.walk(root):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
                out.add(sub.id)
            elif isinstance(sub, ast.Attribute) \
                    and isinstance(sub.ctx, ast.Load) \
                    and isinstance(sub.value, ast.Name) \
                    and sub.value.id == "self":
                out.add("self." + sub.attr)
    return out


def _assign_pairs(stmt: ast.stmt) -> list[tuple[ast.AST, ast.AST | None]]:
    """(target, value_expr | None) pairs; tuple unpacking against a tuple
    literal pairs element-wise (the ``a, b = b, None`` swap idiom), other
    unpacking yields fresh (None-valued) bindings."""
    if isinstance(stmt, ast.Assign):
        out: list[tuple[ast.AST, ast.AST | None]] = []
        for t in stmt.targets:
            if isinstance(t, ast.Tuple):
                if isinstance(stmt.value, ast.Tuple) \
                        and len(stmt.value.elts) == len(t.elts):
                    out += list(zip(t.elts, stmt.value.elts))
                else:
                    out += [(el, None) for el in t.elts]
            else:
                out.append((t, stmt.value))
        return out
    if isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        return [(stmt.target, None)]
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        t = stmt.target
        return [(el, None) for el in
                (t.elts if isinstance(t, ast.Tuple) else [t])]
    return []


def _check_function(ctx: Context, src: Source, fn, donors, factories
                    ) -> list[Finding]:
    calls = [c for c in ast.walk(fn) if isinstance(c, ast.Call)
             and donated_args(c, donors, factories)]
    if not calls:
        return []
    path = ctx.display_path(src)
    cfg = build_cfg(fn)
    out: list[Finding] = []
    reported: set[tuple] = set()

    # state: (aliases, dead) — aliases: frozenset of normalized (a, b)
    # key pairs established by plain `a = b` assignments and killed when
    # either side is rebound; dead: frozenset[(key, donor_key, line)].
    # Keys are locals ('x') and self attributes ('self.x').  Kill-on-
    # rebind keeps the donate-and-rebind loop idiom naturally stable.
    def closure(aliases, key):
        group = {key}
        grew = True
        while grew:
            grew = False
            for a, b in aliases:
                if a in group and b not in group:
                    group.add(b)
                    grew = True
                elif b in group and a not in group:
                    group.add(a)
                    grew = True
        return group

    def flow(node, state):
        stmt = node.stmt
        if stmt is None:
            return {"*": state}
        aliases, dead = state
        # 1) reads of dead values (against the IN state: same-statement
        #    donation has not happened yet)
        for key in _loads(stmt):
            for dk, donor_key, line in dead:
                if dk != key:
                    continue
                rule = "DON001" if key == donor_key else "DON002"
                mark = (rule, stmt.lineno, key)
                if mark not in reported:
                    reported.add(mark)
                    what = "the donated argument" if rule == "DON001" \
                        else "an alias of the value donated"
                    out.append(Finding(
                        rule, path, stmt.lineno,
                        f"{key!r} is {what} at line {line}; its buffer "
                        "is dead after the dispatch — rebind it from "
                        "the result (or drop the reference) first"))
                break
        # 2) donations performed by this statement (the donated key and
        #    everything currently aliasing it die together)
        new_dead = set(dead)
        for sub in (s for root in eval_roots(stmt)
                    for s in ast.walk(root)):
            if isinstance(sub, ast.Call):
                for arg_expr, _p in donated_args(sub, donors, factories):
                    key = _key_of(arg_expr)
                    if key is None:
                        continue
                    for k2 in closure(aliases, key):
                        new_dead.add((k2, key, stmt.lineno))
        # exc edge: the donation is assumed dispatched (conservative — the
        # PR-6 restore hardening exists because a mid-copy failure leaves
        # the buffer dead) but the REBIND below did not happen.  This is
        # what catches `self._c = f(self._c)` serving a dead buffer out of
        # a swallowing except.
        exc_state = (aliases, frozenset(new_dead))
        # 3) assignments: rebinds revive their targets; plain `a = b`
        #    additionally records the alias (unless b was rebound by the
        #    same statement — the swap idiom's None side)
        pairs = _assign_pairs(stmt)
        targets = {tk for t, _v in pairs if (tk := _key_of(t)) is not None}
        if targets:
            new_dead = {(k, dk, ln) for k, dk, ln in new_dead
                        if k not in targets}
            new_alias = {(a, b) for a, b in aliases
                         if a not in targets and b not in targets}
            for t, v in pairs:
                tk = _key_of(t)
                vk = _key_of(v) if v is not None else None
                if tk is not None and vk is not None and vk not in targets \
                        and tk != vk:
                    new_alias.add(tuple(sorted((tk, vk))))
        else:
            new_alias = set(aliases)
        return {"*": (frozenset(new_alias), frozenset(new_dead)),
                "exc": exc_state}

    def join(a, b):
        return (a[0] | b[0], a[1] | b[1])

    IN = solve_forward(cfg, (frozenset(), frozenset()), flow, join)

    # 4) at exit: a dead self-attr ALIAS outlives the frame — the
    #    "stashed on self then donated" trap (the donated key itself is
    #    the caller's rebind-or-drop contract, flagged only on reads)
    state = IN.get(cfg.exit)
    if state is not None:
        _aliases, dead = state
        for k, donor_key, line in dead:
            if k != donor_key and k.startswith("self."):
                mark = ("DON002-exit", line, k)
                if mark not in reported:
                    reported.add(mark)
                    out.append(Finding(
                        "DON002", path, line,
                        f"{k!r} still references the buffer donated "
                        "here at function exit — the next reader gets "
                        "a dead buffer; rebind or clear it"))
    return out


def check(ctx: Context) -> list[Finding]:
    donors, factories = build_registry(ctx)
    out: list[Finding] = []
    for src in ctx.sources:
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(_check_function(
                    ctx, src, node, donors, factories))
    return out
