"""RT: retrace hazards — non-static jit arguments in shape positions.

A jitted kernel whose *traced* argument reaches a shape position
(`jnp.zeros(n)`, `x.reshape(n, -1)`, `jnp.arange(n)`) either raises at
trace time or — when the value arrives as a Python int — silently
recompiles per distinct value. On the serving path one such leak turns
the steady-state "launch + readback" cost into a compile per batch.
The fix is always the same: cover the argument with `static_argnums`/
`static_argnames` (or derive the size from `.shape`, which is static
under the trace).

  RT001  non-static jit argument flows into a shape position

Roots are jit-wrapped functions (decorated `@jax.jit` /
`@partial(jax.jit, ...)`, or wrapped by a module-level assignment like
`shape_route_step = partial(jax.jit, static_argnames=...)(shape_route_step_impl)`).
Hazard = the root's parameters minus its static names. Hazards follow
simple assignment and propagate through calls into callee parameters
(`shape_route_step_impl` hands `kslot` to `compact_fanout_slots` — dropping
`kslot` from the static tuple is flagged *inside the callee*). Deriving
from `.shape`/`.ndim`/`.size`/`len()` clears the hazard: those are
static at trace time. Closure variables are static by construction and
never hazardous.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from tools.analysis.callgraph import (
    FnInfo,
    FuncKey,
    ProjectGraph,
    module_dotted,
    shared_graph,
)
from tools.analysis.core import Checker, Finding, ParsedModule

JIT_NAMES = {"jax.jit", "jit"}
PARTIAL_NAMES = {"functools.partial", "partial"}
STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "nbytes"}

# callable -> indices of its shape-position arguments
SHAPE_ARG0 = {"zeros", "ones", "full", "empty", "arange", "eye",
              "linspace", "iota"}
SHAPE_ARG1 = {"broadcast_to", "tile", "reshape", "full_like"}
SHAPE_METHODS = {"reshape", "broadcast_to", "resize"}

_MESSAGES = {
    "RT001": "non-static jit argument in a shape position (retrace per "
             "value, or a trace-time error on array args) — cover it "
             "with static_argnums/static_argnames or derive the size "
             "from .shape",
}


def _jnp_tail(name: str) -> str:
    """'jax.numpy.zeros' / 'jnp.zeros' / 'numpy.zeros' -> 'zeros'."""
    head, _, tail = name.rpartition(".")
    if head in ("jax.numpy", "jnp", "numpy", "np", "jax.lax", "lax"):
        return tail
    return ""


def _static_names(call: ast.Call, fn_node) -> Set[str]:
    """static_argnames/static_argnums literals -> parameter-name set."""
    out: Set[str] = set()
    params = [a.arg for a in fn_node.args.args + fn_node.args.kwonlyargs]
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                for e in v.elts:
                    if isinstance(e, ast.Constant) and isinstance(
                        e.value, str
                    ):
                        out.add(e.value)
        elif kw.arg == "static_argnums":
            v = kw.value
            nums: List[int] = []
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                nums = [v.value]
            elif isinstance(v, (ast.Tuple, ast.List)):
                nums = [
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, int)
                ]
            for n in nums:
                if 0 <= n < len(params):
                    out.add(params[n])
    return out


class RetraceChecker(Checker):
    name = "retrace"
    codes = dict(_MESSAGES)

    def begin(self, modules: Sequence[ParsedModule]) -> None:
        g = self._graph = shared_graph(modules)
        # (func key) -> hazardous parameter names, grown to a fixpoint
        self._hazard: Dict[FuncKey, Set[str]] = {}
        self._roots: List[Tuple[FnInfo, Set[str]]] = []
        for info in g.infos:
            statics = self._root_statics(info)
            if statics is not None:
                self._roots.append((info, statics))
        for mod in modules:
            dn = module_dotted(mod.rel)
            for stmt in mod.tree.body:
                if not isinstance(stmt, ast.Assign):
                    continue
                hit = self._wrapped_impl(dn, stmt.value)
                if hit is None:
                    continue
                impl_key, jit_call = hit
                for impl in g.funcs.get(impl_key, []):
                    self._roots.append(
                        (impl, _static_names(jit_call, impl.node))
                    )
        for info, statics in self._roots:
            params = [
                a.arg
                for a in info.node.args.args + info.node.args.kwonlyargs
            ]
            hazard = {
                p for p in params
                if p not in statics and p not in ("self", "cls")
            }
            if hazard:
                self._hazard.setdefault(info.key, set()).update(hazard)
        # fixpoint: hazards flow through call sites into callees
        for _ in range(12):
            grew = False
            for key in list(self._hazard):
                for info in g.funcs.get(key, []):
                    if self._propagate(info):
                        grew = True
            if not grew:
                break

    def _root_statics(self, info: FnInfo) -> Optional[Set[str]]:
        """Static names when `info` is jit-decorated, else None."""
        g = self._graph
        for dec in info.node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            name = g.call_name(info.dn, target)
            if name in JIT_NAMES:
                call = dec if isinstance(dec, ast.Call) else ast.Call(
                    func=dec, args=[], keywords=[]
                )
                return _static_names(call, info.node)
            if (
                isinstance(dec, ast.Call)
                and name in PARTIAL_NAMES
                and dec.args
                and g.call_name(info.dn, dec.args[0]) in JIT_NAMES
            ):
                return _static_names(dec, info.node)
        return None

    def _wrapped_impl(
        self, dn: str, value: ast.AST
    ) -> Optional[Tuple[FuncKey, ast.Call]]:
        """`[wrap(...)](partial(jax.jit, ...)(impl))` / `jax.jit(impl)`
        anywhere in an assignment RHS -> (impl key, the jit call)."""
        g = self._graph
        for node in ast.walk(value):
            if not isinstance(node, ast.Call):
                continue
            name = g.call_name(dn, node.func)
            if name in JIT_NAMES and node.args:
                targets = g.ref_targets(dn, node.args[0])
                for t in targets:
                    if t in g.funcs:
                        return t, node
            if isinstance(node.func, ast.Call):
                inner = g.call_name(dn, node.func.func)
                if (
                    inner in PARTIAL_NAMES
                    and node.func.args
                    and g.call_name(dn, node.func.args[0]) in JIT_NAMES
                    and node.args
                ):
                    for t in g.ref_targets(dn, node.args[0]):
                        if t in g.funcs:
                            return t, node.func
        return None

    # -- hazard propagation / screening ------------------------------------
    def _hazard_names(self, info: FnInfo) -> Set[str]:
        return self._hazard.get(info.key, set())

    def _local_hazards(self, info: FnInfo) -> Dict[ast.Call, List[str]]:
        """Walk one function: returns shape-position violations, and as a
        side effect records hazard propagation into callees."""
        g = self._graph
        dn = info.dn
        hazard = set(self._hazard_names(info))
        cleared: Set[str] = set()
        violations: Dict[ast.Call, List[str]] = {}

        def expr_hazards(e: ast.AST) -> List[str]:
            out = []
            for sub in ast.walk(e):
                if isinstance(sub, ast.Attribute) or (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Name)
                    and sub.func.id == "len"
                ):
                    # `.shape[0]` / `len(x)` subtrees are static
                    return []
            for sub in ast.walk(e):
                if isinstance(sub, ast.Name) and sub.id in hazard \
                        and sub.id not in cleared:
                    out.append(sub.id)
            return out

        def check_call(node: ast.Call) -> None:
            name = g.call_name(dn, node.func)
            tail = _jnp_tail(name)
            shape_args: List[ast.AST] = []
            if tail in SHAPE_ARG0 and node.args:
                shape_args.append(node.args[0])
                if tail == "arange" and len(node.args) > 1:
                    shape_args.extend(node.args[1:3])
            elif tail in SHAPE_ARG1 and len(node.args) > 1:
                shape_args.extend(node.args[1:])
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in SHAPE_METHODS
                and not _jnp_tail(name)  # method call, not jnp.reshape
            ):
                shape_args.extend(node.args)
            for kw in node.keywords:
                if kw.arg == "shape":
                    shape_args.append(kw.value)
            hits: List[str] = []
            for a in shape_args:
                hits.extend(expr_hazards(a))
            if hits:
                violations[node] = sorted(set(hits))
            # propagate hazards into callee params
            targets = [t for t in g.ref_targets(dn, node.func)
                       if t in g.funcs]
            for t in targets:
                for callee in g.funcs.get(t, []):
                    cparams = [
                        a.arg
                        for a in callee.node.args.args
                        + callee.node.args.kwonlyargs
                    ]
                    is_method = bool(cparams) and cparams[0] in (
                        "self", "cls"
                    )
                    shift = 1 if (
                        is_method and isinstance(node.func, ast.Attribute)
                    ) else 0
                    names: List[str] = []
                    for i, arg in enumerate(node.args):
                        if expr_hazards(arg) and i + shift < len(cparams):
                            names.append(cparams[i + shift])
                    for kw in node.keywords:
                        if kw.arg and kw.arg in cparams \
                                and expr_hazards(kw.value):
                            names.append(kw.arg)
                    if names:
                        cur = self._hazard.setdefault(t, set())
                        self._grew |= not set(names) <= cur
                        cur.update(names)

        def track_assign(s: ast.Assign) -> None:
            hz = expr_hazards(s.value)
            names: List[ast.Name] = []
            for t in s.targets:
                if isinstance(t, ast.Name):
                    names.append(t)
                elif isinstance(t, (ast.Tuple, ast.List)):
                    names.extend(
                        e for e in t.elts if isinstance(e, ast.Name)
                    )
            for n in names:
                if hz:
                    hazard.add(n.id)
                    cleared.discard(n.id)
                else:
                    cleared.add(n.id)

        def walk(stmts) -> None:
            for s in stmts:
                if isinstance(
                    s, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
                ):
                    continue
                if isinstance(s, ast.Assign):
                    # order matters: screen the RHS calls against the
                    # PRE-assignment hazard set, then update it
                    for sub in ast.walk(s.value):
                        if isinstance(sub, ast.Call):
                            check_call(sub)
                    track_assign(s)
                    continue
                for sub in ast.walk(s):
                    if isinstance(sub, ast.Call):
                        check_call(sub)
                for attr in ("body", "orelse", "finalbody"):
                    nested = getattr(s, attr, None)
                    if nested:
                        # hazard/cleared tracking for nested assigns;
                        # calls were already screened by the ast.walk
                        for sub in nested:
                            if isinstance(sub, ast.Assign):
                                track_assign(sub)
        walk(info.node.body)
        return violations

    def finalize(self) -> Iterable[Finding]:
        self._grew = False
        findings: List[Finding] = []
        seen: Set[Tuple[str, int, str]] = set()
        done: Set[int] = set()
        for key in list(self._hazard):
            for info in self._graph.funcs.get(key, []):
                if id(info.node) in done:
                    continue
                done.add(id(info.node))
                for call, names in self._local_hazards(info).items():
                    k = (info.mod.rel, call.lineno, ",".join(names))
                    if k in seen:
                        continue
                    seen.add(k)
                    detail = ",".join(names)
                    findings.append(Finding(
                        code="RT001", path=info.mod.rel, line=call.lineno,
                        symbol=info.symbol, detail=detail,
                        message=f"{detail}: {_MESSAGES['RT001']}",
                    ))
        return findings

    def _propagate(self, info: FnInfo) -> bool:
        self._grew = False
        self._local_hazards(info)
        return self._grew
