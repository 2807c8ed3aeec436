"""BP: sender/receiver symmetry for cluster protocols.

The reference broker freezes every inter-node API in a versioned BPAPI
module and CI fails when a call site and a handler disagree. Here the
frozen tables live in emqx_tpu/proto/registry.py (`kind="proto"` for
the rpc method tables, `kind="tags"` with a `#pos0`/`#key=K` source
fragment for the tuple-discriminator families), and this checker does
the static cross-check:

- BP001 — an rpc send site (`*.rpc.call/cast/multicall(peer, api,
  method, ...)`, `rpc_call(peer, api, method, ...)`) whose (api, method)
  pair is in NO registered proto version: the receiver will raise at
  dispatch, but only at runtime, on a peer.
- BP002 — a registered (api, method) that no local code ever sends.
  Either dead protocol surface or a receiver-only method; the latter is
  declared in `BPAPI_SERVE_ONLY` next to the registry table, so the
  exemption is versioned with the contract instead of living in the
  checker.
- BP003 — the in-code proto tables (`rpc.registry.register(api, v,
  {method: handler})`) drifted from the registry declaration: the
  frozen table and the served table must spell the same methods.
- BP004 — tag-family asymmetry: a tag sent with no handler compare, a
  registered tag nobody sends, or a tuple sent at a bus boundary whose
  discriminator is registered nowhere. A tag added on one side only is
  exactly the rolling-upgrade wreck BPAPI exists to prevent.

Method names that reach the rpc site through a variable propagate one
level through the enclosing function's parameter (the `_replicate(
"add_route")` / `_shared_cast("join")` indirections), so the real
sender set is visible without executing anything.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from emqx_tpu.proto.digest import proto_digest
from tools.analysis.core import Checker, Finding, ParsedModule, dotted_name
from tools.analysis.checkers.wire_common import (
    Registration,
    extract_registrations,
    module_index,
    resolve_literal,
    toplevel_assigns,
)

RPC_METHODS = frozenset({"call", "call_on", "cast", "multicall"})

# call names that put a tuple on the cluster wire
TUPLE_BOUNDARY = frozenset({
    "send", "sendall", "cast", "enqueue", "send_frame", "_send_frame",
})


def _call_name(node: ast.Call) -> Optional[str]:
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def _str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class _TagFamily:
    """One registered tuple-discriminator family."""

    def __init__(self, reg: Registration, handler_rel: str, frag: str):
        self.reg = reg
        self.handler_rel = handler_rel
        self.key: Optional[str] = None  # None => position-0 family
        if frag.startswith("key="):
            self.key = frag[4:]
        self.tags: Set[str] = set()
        if isinstance(reg.structure, dict):
            self.tags = {str(k) for k in reg.structure.values()}
        self.sent: Set[str] = set()
        self.handled: Set[str] = set()


class BpapiSymmetryChecker(Checker):
    name = "bpapi"
    codes = {
        "BP001": "rpc send site targets an unregistered (api, method)",
        "BP002": "registered rpc method has no sender (and is not "
                 "declared serve-only)",
        "BP003": "in-code proto table drifted from the registry BPAPI",
        "BP004": "cluster tag family sender/handler asymmetry",
    }

    def __init__(self):
        self._modules: Sequence[ParsedModule] = ()
        self._by_rel: Dict[str, ParsedModule] = {}
        # every kind="proto" registration with its own table and its
        # module's BPAPI_SERVE_ONLY (fixture trees carry several)
        self._protos: List[
            Tuple[Registration, Dict[str, Dict[int, Tuple[str, ...]]],
                  Set[Tuple[str, str]]]
        ] = []
        self._families: List[_TagFamily] = []
        # sent (api, method) -> first (mod, line) seen
        self._sent: Dict[Tuple[str, str], Tuple[ParsedModule, int]] = {}
        # in-code rpc.registry.register tables: (api, v) -> (methods, site)
        self._code_tables: Dict[
            Tuple[str, int], Tuple[Set[str], ParsedModule, int]
        ] = {}
        # pending one-level propagations: (func_name, param_pos, api, site)
        self._pending: List[Tuple[str, int, str, ParsedModule, int]] = []

    # -- begin: load registry declarations --------------------------------
    def begin(self, modules: Sequence[ParsedModule]) -> None:
        self.__init__()
        self._modules = modules
        self._by_rel = module_index(modules)
        for reg in extract_registrations(modules):
            if reg.kind == "proto" and isinstance(reg.structure, dict):
                bpapi = {
                    str(api): {
                        int(v): tuple(methods)
                        for v, methods in vers.items()
                    }
                    for api, vers in reg.structure.items()
                }
                serve_only: Set[Tuple[str, str]] = set()
                only = toplevel_assigns(reg.mod).get("BPAPI_SERVE_ONLY")
                if only is not None:
                    val = resolve_literal(reg.mod, only)
                    if isinstance(val, (set, frozenset, list, tuple)):
                        serve_only = {
                            tuple(t) for t in val
                            if isinstance(t, (list, tuple)) and len(t) == 2
                        }
                self._protos.append((reg, bpapi, serve_only))
            elif reg.kind == "tags":
                path, _symbol, frag = reg.source_parts()
                if frag == "pos0" or frag.startswith("key="):
                    self._families.append(_TagFamily(reg, path, frag))
        for mod in modules:
            self._collect_rpc_sites(mod)
            self._collect_code_tables(mod)
            self._collect_tuples(mod)
        self._propagate()
        for fam in self._families:
            self._collect_handlers(fam)

    # -- rpc send sites ----------------------------------------------------
    def _collect_rpc_sites(self, mod: ParsedModule) -> None:
        funcs = {
            n.name: n
            for n in ast.walk(mod.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(mod.tree):
            for child in ast.iter_child_nodes(parent):
                parents[child] = parent

        def enclosing_func(node: ast.AST):
            cur = parents.get(node)
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    return cur
                cur = parents.get(cur)
            return None

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            is_rpc = False
            if isinstance(fn, ast.Attribute) and fn.attr in RPC_METHODS:
                recv = dotted_name(fn.value) or ""
                is_rpc = "rpc" in recv.split(".")
            elif isinstance(fn, ast.Name) and "rpc" in fn.id:
                is_rpc = True
            if not is_rpc:
                continue
            # api = first positional str const; method = the next arg
            api = None
            method_node = None
            for i, arg in enumerate(node.args):
                s = _str_const(arg)
                if s is not None:
                    api = s
                    if i + 1 < len(node.args):
                        method_node = node.args[i + 1]
                    break
            if api is None or method_node is None:
                continue
            method = _str_const(method_node)
            if method is not None:
                self._sent.setdefault((api, method), (mod, node.lineno))
                continue
            if isinstance(method_node, ast.Name):
                # the send often sits in a worker closure (`def one(p)`)
                # with the method a free variable of the OUTER
                # indirection (`_replicate`, `_shared_cast`): walk out
                # until a function binds it as a parameter
                outer = enclosing_func(node)
                while outer is not None:
                    params = [a.arg for a in outer.args.args]
                    if method_node.id in params:
                        self._pending.append((
                            outer.name, params.index(method_node.id),
                            api, mod, node.lineno,
                        ))
                        break
                    outer = enclosing_func(outer)

    def _propagate(self) -> None:
        """One-level constant propagation: str consts at the matching
        positional index of call sites of the indirection function."""
        for fname, ppos, api, site_mod, site_line in self._pending:
            for mod in self._modules:
                for node in ast.walk(mod.tree):
                    if not isinstance(node, ast.Call):
                        continue
                    fn = node.func
                    if isinstance(fn, ast.Attribute) and fn.attr == fname:
                        argpos = ppos - 1  # self-call: drop the self param
                    elif isinstance(fn, ast.Name) and fn.id == fname:
                        argpos = ppos
                    else:
                        continue
                    if 0 <= argpos < len(node.args):
                        m = _str_const(node.args[argpos])
                        if m is not None:
                            self._sent.setdefault(
                                (api, m), (mod, node.lineno)
                            )

    # -- in-code proto tables ----------------------------------------------
    def _collect_code_tables(self, mod: ParsedModule) -> None:
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call) and len(node.args) >= 3):
                continue
            fn = node.func
            if not (isinstance(fn, ast.Attribute) and fn.attr == "register"):
                continue
            recv = dotted_name(fn.value) or ""
            if "registry" not in recv.split("."):
                continue  # metric/fault registries etc. are not protos
            api = _str_const(node.args[0])
            ver = node.args[1]
            table = node.args[2]
            if (
                api is None
                or not isinstance(ver, ast.Constant)
                or not isinstance(ver.value, int)
                or not isinstance(table, ast.Dict)
            ):
                continue
            methods = set()
            ok = True
            for k in table.keys:
                s = _str_const(k) if k is not None else None
                if s is None:
                    ok = False
                    break
                methods.add(s)
            if ok:
                self._code_tables[(api, ver.value)] = (
                    methods, mod, node.lineno
                )

    # -- tag families -------------------------------------------------------
    def _tuple_head(self, t: ast.Tuple) -> Optional[str]:
        if t.elts:
            return _str_const(t.elts[0])
        return None

    def _collect_tuples(self, mod: ParsedModule) -> None:
        pos0_universe = set()
        keys = {}
        for fam in self._families:
            if fam.key is None:
                pos0_universe |= fam.tags
            else:
                keys[fam.key] = fam
        # modules in scope for the sent-unregistered check: family
        # handler modules + modules that demonstrably speak a family
        in_scope = any(fam.handler_rel == mod.rel for fam in self._families)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Tuple):
                head = self._tuple_head(node)
                if head is None:
                    continue
                # sends (for the no-sender direction): any tuple literal
                # counts — replies are built into a variable before the
                # send call, so boundary-arg position can't be required
                for fam in self._families:
                    if fam.key is None:
                        if head in fam.tags:
                            fam.sent.add(head)
                            in_scope = True
                    elif head == fam.key and len(node.elts) > 1:
                        tag = _str_const(node.elts[1])
                        if tag is not None:
                            fam.sent.add(tag)
                            in_scope = True
        if not (in_scope and self._families):
            return
        # sent-unregistered: tuples handed DIRECTLY to a wire boundary
        # in a module that speaks the protocol
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if _call_name(node) not in TUPLE_BOUNDARY:
                continue
            for arg in node.args:
                if not isinstance(arg, ast.Tuple):
                    continue
                head = self._tuple_head(arg)
                if head is None or head in keys:
                    # unregistered tags UNDER a key are caught at the
                    # family level (fam.sent - fam.tags)
                    continue
                if pos0_universe and head not in pos0_universe:
                    self._unregistered_head(head, mod, arg.lineno)

    def _collect_handlers(self, fam: _TagFamily) -> None:
        mod = self._by_rel.get(fam.handler_rel)
        if mod is None:
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Compare):
                continue
            consts: List[str] = []
            for side in [node.left, *node.comparators]:
                s = _str_const(side)
                if s is not None:
                    consts.append(s)
                elif isinstance(side, (ast.Tuple, ast.List, ast.Set)):
                    for e in side.elts:
                        es = _str_const(e)
                        if es is not None:
                            consts.append(es)
            for s in consts:
                if s in fam.tags:
                    fam.handled.add(s)

    def _unregistered_head(self, head, mod, line) -> None:
        self._deferred_findings().append(Finding(
            code="BP004",
            path=mod.rel,
            line=line,
            symbol="<module>",
            detail=f"head:{head}:sent-unregistered",
            message=(
                f"tuple with discriminator {head!r} reaches a wire "
                "boundary but no registered tag family covers it"
            ),
        ))

    def _deferred_findings(self) -> List[Finding]:
        if not hasattr(self, "_deferred_list"):
            self._deferred_list: List[Finding] = []
        return self._deferred_list

    # -- finalize -----------------------------------------------------------
    def finalize(self) -> Iterable[Finding]:
        yield from self._deferred_findings()
        if self._protos:
            yield from self._check_bpapi()
        for fam in self._families:
            yield from self._check_family(fam)

    def _check_bpapi(self) -> Iterable[Finding]:
        registered_pairs = {
            (api, m)
            for _reg, bpapi, _so in self._protos
            for api, vers in bpapi.items()
            for methods in vers.values()
            for m in methods
        }
        # BP001: sends with no registration (unknown api included)
        for (api, method), (mod, line) in sorted(self._sent.items()):
            if (api, method) not in registered_pairs:
                yield Finding(
                    code="BP001",
                    path=mod.rel,
                    line=line,
                    symbol="<module>",
                    detail=f"{api}.{method}",
                    message=(
                        f"rpc send targets {api}.{method} but no "
                        f"registered {api!r} proto version declares it"
                    ),
                )
        # BP002: registered methods nobody sends
        sent_pairs = set(self._sent)
        for reg, bpapi, serve_only in self._protos:
            for api, vers in sorted(bpapi.items()):
                union = {m for methods in vers.values() for m in methods}
                for method in sorted(union):
                    if (api, method) in sent_pairs:
                        continue
                    if (api, method) in serve_only:
                        continue
                    yield Finding(
                        code="BP002",
                        path=reg.mod.rel,
                        line=reg.lineno,
                        symbol="<module>",
                        detail=f"{api}.{method}",
                        message=(
                            f"registered proto method {api}.{method} has "
                            "no local send site — dead surface, or add it "
                            "to BPAPI_SERVE_ONLY with a justification"
                        ),
                    )
        # BP003: in-code tables vs registry tables (only when the tree
        # actually serves protos — fixtures without a node are exempt)
        if not self._code_tables:
            return
        declared = {}
        declaring_reg = {}
        for reg, bpapi, _so in self._protos:
            for api, vers in bpapi.items():
                for v, methods in vers.items():
                    declared[(api, v)] = set(methods)
                    declaring_reg[(api, v)] = (reg, bpapi)
        for key in sorted(set(declared) | set(self._code_tables)):
            api, v = key
            if key not in self._code_tables:
                reg, _bpapi = declaring_reg[key]
                yield Finding(
                    code="BP003",
                    path=reg.mod.rel,
                    line=reg.lineno,
                    symbol="<module>",
                    detail=f"{api}.v{v}:unserved",
                    message=(
                        f"registry declares {api} v{v} but no in-code "
                        "proto table registers it"
                    ),
                )
                continue
            methods, mod, line = self._code_tables[key]
            if key not in declared:
                yield Finding(
                    code="BP003",
                    path=mod.rel,
                    line=line,
                    symbol="<module>",
                    detail=f"{api}.v{v}:undeclared",
                    message=(
                        f"in-code proto table registers {api} v{v} but "
                        "the registry BPAPI does not declare that version"
                    ),
                )
            elif methods != declared[key]:
                missing = sorted(declared[key] - methods)
                extra = sorted(methods - declared[key])
                _reg, bpapi = declaring_reg[key]
                yield Finding(
                    code="BP003",
                    path=mod.rel,
                    line=line,
                    symbol="<module>",
                    detail=f"{api}.v{v}",
                    message=(
                        f"proto table {api} v{v} drifted from the "
                        f"registry: missing={missing} extra={extra} "
                        f"(registry digest {proto_digest(bpapi)})"
                    ),
                )

    def _check_family(self, fam: _TagFamily) -> Iterable[Finding]:
        reg = fam.reg
        for tag in sorted(fam.sent - fam.tags):
            # universe-filtered collection can't produce these for pos0
            # (filtered on membership); key= families can
            yield Finding(
                code="BP004",
                path=reg.mod.rel,
                line=reg.lineno,
                symbol="<module>",
                detail=f"{reg.name}:{tag}:sent-unregistered",
                message=(
                    f"tag {tag!r} is sent but not registered in "
                    f"{reg.name!r}"
                ),
            )
        for tag in sorted(fam.tags - fam.sent):
            yield Finding(
                code="BP004",
                path=reg.mod.rel,
                line=reg.lineno,
                symbol="<module>",
                detail=f"{reg.name}:{tag}:no-sender",
                message=(
                    f"registered tag {tag!r} of {reg.name!r} has no "
                    "send site in the tree"
                ),
            )
        for tag in sorted(fam.tags - fam.handled):
            yield Finding(
                code="BP004",
                path=reg.mod.rel,
                line=reg.lineno,
                symbol="<module>",
                detail=f"{reg.name}:{tag}:no-handler",
                message=(
                    f"registered tag {tag!r} of {reg.name!r} is never "
                    f"compared against in its handler module "
                    f"{fam.handler_rel} — a sent op nobody dispatches"
                ),
            )
