"""Device-hygiene rules: hidden syncs, retrace hazards, jit closures.

The fused proposal paths (PR 1/3/4/6) are one device program per ask;
their perf claims are CI-gated.  A stray ``.item()`` or ``np.asarray``
on a JAX value is a hidden blocking device->host sync; a ``jnp`` call
under an eager Python loop is a per-iteration dispatch (and a retrace
hazard when shapes vary); a jitted entry point closing over mutable
Python state silently bakes a stale value into the compiled program.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Set

from repro.analysis.lint import (Finding, Module, Rule, call_name,
                                 terminal_name)
from repro.analysis.rules import register

# fused-path files: where device values flow and host syncs hide
_DEVICE_FILES = ("gp.py", "tpe.py", "scoring.py", "studybank.py",
                 "kmeans.py", "kernels")

# call-name shapes that produce device (JAX) values in this repo
_DEVICE_TERMINAL_PREFIXES = ("bank_", "fused_", "fit_hypers", "_dispatch")
_HOST_TERMINALS = {"device_get"}        # jax.device_get returns numpy


def _is_device_call(call: ast.Call) -> bool:
    name = call_name(call)
    root = name.split(".", 1)[0]
    term = terminal_name(call)
    if term in _HOST_TERMINALS:
        return False
    if root in ("jnp", "jax", "lax"):
        return True
    return any(term.startswith(p) for p in _DEVICE_TERMINAL_PREFIXES)


def _assign_targets(node) -> List[str]:
    out: List[str] = []

    def collect(t):
        if isinstance(t, ast.Name):
            out.append(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                collect(e)

    if isinstance(node, ast.Assign):
        for t in node.targets:
            collect(t)
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        collect(node.target)
    return out


def _walk_scope(scope: ast.AST, module_level: bool):
    """Walk ``scope`` without descending into *other* function bodies:
    taint is per innermost function, so a name assigned from a device
    call in one function can't flag an unrelated same-named host value
    elsewhere in the module."""
    stack = [scope]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if (isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and (module_level or child is not scope)):
                continue
            stack.append(child)


def _device_names(scope: ast.AST, module_level: bool = False) -> Set[str]:
    """Names in ``scope`` assigned (directly or via tuple unpack) from a
    device-producing call.  Two passes so a name defined later in source
    order still taints earlier textual uses in loops."""
    tainted: Set[str] = set()
    for _ in range(2):
        for node in _walk_scope(scope, module_level):
            if not isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                continue
            value = node.value
            if value is None:
                continue
            if isinstance(value, ast.Call) and (
                    call_name(value) in ("float", "int", "bool", "len")
                    or terminal_name(value) in ("device_get", "item",
                                                "tolist")):
                # host extraction: the result is a Python/numpy host
                # value, so the assignment *clears* any earlier taint
                for t in _assign_targets(node):
                    tainted.discard(t)
                continue
            feeds = any(
                (isinstance(n, ast.Call) and _is_device_call(n))
                or (isinstance(n, ast.Name) and n.id in tainted
                    and isinstance(n.ctx, ast.Load))
                for n in ast.walk(value))
            if feeds:
                tainted.update(_assign_targets(node))
    return tainted


def _jit_decorated(fn) -> bool:
    for dec in fn.decorator_list:
        name = dotted = ""
        if isinstance(dec, ast.Call):
            dotted = call_name(dec)
            if dotted in ("functools.partial", "partial") and dec.args:
                first = dec.args[0]
                name = (call_name(first) if isinstance(first, ast.Call)
                        else (first.attr if isinstance(first, ast.Attribute)
                              else getattr(first, "id", "")))
                if isinstance(first, ast.Attribute):
                    name = f"{getattr(first.value, 'id', '')}.{first.attr}"
            else:
                name = dotted
        elif isinstance(dec, ast.Attribute):
            name = f"{getattr(dec.value, 'id', '')}.{dec.attr}"
        elif isinstance(dec, ast.Name):
            name = dec.id
        if name in ("jax.jit", "jit"):
            return True
    return False


@register
class HostSyncRule(Rule):
    id = "REPRO-J101"
    family = "device-hygiene"
    scopes = _DEVICE_FILES
    description = (".item()/float()/np.asarray on a JAX value in a fused "
                   "proposal path — each is a hidden blocking device sync")
    rationale = ("The bank serving steady state is transfer-audited "
                 "(sanitizers.no_transfer); an implicit device->host "
                 "read stalls the dispatch pipeline.  Use "
                 "jax.device_get() at the one deliberate exit point, or "
                 "keep the value on device.")

    def check(self, mod: Module) -> Iterable[Finding]:
        taint_cache: dict = {}

        def tainted_for(node: ast.AST) -> Set[str]:
            fn = mod.enclosing_function(node)
            key = fn if fn is not None else mod.tree
            if key not in taint_cache:
                taint_cache[key] = _device_names(
                    key, module_level=fn is None)
            return taint_cache[key]

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            term = terminal_name(node)
            name = call_name(node)
            msg = None
            if term == "item" and isinstance(node.func, ast.Attribute):
                msg = (".item() forces a device sync — use "
                       "jax.device_get() at the designed exit point")
            elif name in ("np.asarray", "np.array", "numpy.asarray",
                          "numpy.array", "float") and node.args:
                arg = node.args[0]
                dev = ((isinstance(arg, ast.Name)
                        and arg.id in tainted_for(node))
                       or (isinstance(arg, ast.Call)
                           and _is_device_call(arg)))
                np_call = (name == "float"
                           and isinstance(arg, ast.Call)
                           and call_name(arg).split(".", 1)[0]
                           in ("np", "numpy", "jnp"))
                if dev:
                    msg = (f"{name}() on a device value is an "
                           "implicit device->host transfer — use "
                           "jax.device_get()")
                elif np_call:
                    msg = ("float() over an array-API call in a "
                           "fused-path file — hidden sync if the "
                           "value is a JAX array; baseline if "
                           "provably host")
            if msg is not None:
                yield self.finding(mod, node, msg)


@register
class EagerLoopDispatchRule(Rule):
    id = "REPRO-J102"
    family = "device-hygiene"
    scopes = _DEVICE_FILES
    description = ("jnp/jax call under an eager Python for/while/"
                   "comprehension — per-iteration dispatch and retrace "
                   "hazard")
    rationale = ("PR 6 replaced every per-study Python loop with one "
                 "vmap'd program (74.6x at B=256).  Loops *inside* "
                 "jax.jit unroll at trace time and are exempt; eager "
                 "loops dispatch (and may retrace) every iteration.")

    def check(self, mod: Module) -> Iterable[Finding]:
        loops = [n for n in ast.walk(mod.tree)
                 if isinstance(n, (ast.For, ast.While, ast.ListComp,
                                   ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp))]
        for loop in loops:
            fn = mod.enclosing_function(loop)
            if fn is not None and (_jit_decorated(fn)
                                   or "kernel" in fn.name):
                # jit bodies and Pallas kernel bodies trace once: their
                # Python loops unroll at trace time, not eager dispatch
                continue
            for node in ast.walk(loop):
                if (isinstance(node, ast.Attribute)
                        and isinstance(node.value, ast.Name)
                        and node.value.id in ("jnp", "jax", "lax")):
                    yield self.finding(
                        mod, loop,
                        f"{node.value.id}.{node.attr} inside an eager "
                        "Python loop — one device dispatch per "
                        "iteration; batch/vmap it or hoist out")
                    break   # one finding per loop, not per op


@register
class JitClosureRule(Rule):
    id = "REPRO-J103"
    family = "device-hygiene"
    scopes = _DEVICE_FILES
    description = ("jax.jit entry point closing over enclosing-function "
                   "locals — non-static Python state baked in at trace "
                   "time")
    rationale = ("A jitted function that closes over a mutable local "
                 "keeps serving the value captured at first trace; "
                 "rebinding the local silently does nothing.  Pass such "
                 "values as (static) arguments instead.  ALL_CAPS "
                 "constants are exempt.")

    def check(self, mod: Module) -> Iterable[Finding]:
        module_names = self._module_bindings(mod.tree)
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not _jit_decorated(fn):
                continue
            outer = mod.enclosing_function(fn)
            if outer is None:
                continue    # module-level entry point: no function closure
            enclosing_locals: Set[str] = set()
            cur = outer
            while cur is not None:
                enclosing_locals |= self._local_bindings(cur)
                cur = mod.enclosing_function(cur)
            own = self._local_bindings(fn) | {
                a.arg for a in (fn.args.args + fn.args.kwonlyargs
                                + fn.args.posonlyargs)}
            if fn.args.vararg:
                own.add(fn.args.vararg.arg)
            if fn.args.kwarg:
                own.add(fn.args.kwarg.arg)
            seen: Set[str] = set()
            for node in ast.walk(fn):
                if (isinstance(node, ast.Name)
                        and isinstance(node.ctx, ast.Load)
                        and node.id not in own
                        and node.id not in module_names
                        and node.id in enclosing_locals
                        and not node.id.isupper()
                        and node.id not in seen
                        and node.id not in _builtin_names()):
                    seen.add(node.id)
                    yield self.finding(
                        mod, node,
                        f"jitted {fn.name}() closes over enclosing-"
                        f"function local {node.id!r} — captured once at "
                        "trace time; pass it as a (static) argument")

    @staticmethod
    def _local_bindings(fn) -> Set[str]:
        out: Set[str] = set()
        for a in (fn.args.args + fn.args.kwonlyargs + fn.args.posonlyargs):
            out.add(a.arg)
        if fn.args.vararg:
            out.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            out.add(fn.args.kwarg.arg)
        for node in ast.walk(fn):
            out.update(_assign_targets(node))
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)) and node is not fn:
                out.add(node.name)
            elif isinstance(node, ast.For):
                out.update(_assign_targets_of(node.target))
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                for al in node.names:
                    out.add((al.asname or al.name).split(".")[0])
            elif isinstance(node, ast.withitem) and node.optional_vars:
                out.update(_assign_targets_of(node.optional_vars))
            elif isinstance(node, ast.comprehension):
                out.update(_assign_targets_of(node.target))
        return out

    @staticmethod
    def _module_bindings(tree) -> Set[str]:
        out: Set[str] = set()
        for node in tree.body:
            out.update(_assign_targets(node))
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                out.add(node.name)
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                for al in node.names:
                    out.add((al.asname or al.name).split(".")[0])
        return out


def _assign_targets_of(t) -> Set[str]:
    out: Set[str] = set()
    if isinstance(t, ast.Name):
        out.add(t.id)
    elif isinstance(t, (ast.Tuple, ast.List)):
        for e in t.elts:
            out |= _assign_targets_of(e)
    return out


def _builtin_names() -> Set[str]:
    import builtins
    return set(dir(builtins))
