"""jit-staleness: jitted/pallas bodies must not read mutable host state.

Incident: the flash-attention backward once selected its algorithm from a
``BWD_MODE`` module global read at trace time. The global participated
in no jit cache key, so flipping it silently kept serving the OLD
compiled program — results changed or didn't depending on what had been
traced first (the PR-2 staleness class; the fix made every knob an
explicit ``KernelConfig`` argument that provably re-traces). The same
trap generalizes to ``Settings.*``: a read inside a jitted body bakes
the value of the FIRST trace into every later call.

The rule finds jitted functions (``@jax.jit``/``@partial(jax.jit, …)``
decorators, ``name = jax.jit(fn)`` bindings, kernels passed to
``pallas_call``/``shard_map``) and flags, anywhere in their bodies
(including nested defs — those trace inline):

- ``Settings.X`` attribute reads;
- reads of module globals that are REBOUND at runtime (named in a
  ``global`` statement, or assigned more than once at module level) —
  single-assignment module constants are static and fine;
- host syncs on traced values — ``.item()``, ``float(x)``,
  ``np.asarray``/``np.array``, ``jax.device_get`` — which either crash
  at trace time or silently pin a constant; inside the fused-round and
  submesh programs they also break the no-host-sync dispatch contract.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set

from p2pfl_tpu.analysis.engine import FuncDef, Rule, SourceModule, dotted_name, node_pos
from p2pfl_tpu.analysis.findings import Finding

_JIT_NAMES = {"jit", "jax.jit", "pjit", "jax.pjit", "pjit.pjit"}
_PARTIAL_NAMES = {"partial", "functools.partial"}
#: call wrappers whose first argument is traced as a device program —
#: pallas kernels and shard_map bodies
_KERNEL_WRAPPER_LASTS = {"pallas_call", "shard_map"}
_HOST_SYNC_CALLS = {
    "np.asarray",
    "np.array",
    "numpy.asarray",
    "numpy.array",
    "jax.device_get",
}


def _is_jit_decorator(dec: ast.AST) -> bool:
    name = dotted_name(dec)
    if name in _JIT_NAMES:
        return True
    if isinstance(dec, ast.Call):
        func = dotted_name(dec.func)
        last = func.rsplit(".", 1)[-1] if func else None
        if func in _JIT_NAMES or last in _KERNEL_WRAPPER_LASTS:
            return True
        if func in _PARTIAL_NAMES and dec.args:
            inner = dotted_name(dec.args[0])
            if inner in _JIT_NAMES:
                return True
            # @partial(shard_map, mesh=…):
            # the decorated def IS the per-shard device program
            inner_last = inner.rsplit(".", 1)[-1] if inner else None
            if inner_last in _KERNEL_WRAPPER_LASTS:
                return True
    return False


def _jitted_functions(tree: ast.Module) -> Dict[str, FuncDef]:
    """name → def for every function traced by jit/pallas/shard_map."""
    defs: Dict[str, List[FuncDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)

    # one-hop indirection: ``kernel = partial(_flash_kernel, …)`` aliases
    partial_aliases: Dict[str, Set[str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target = node.targets[0]
            if isinstance(target, ast.Name):
                wrapped = _partial_target(node.value)
                if wrapped is not None:
                    partial_aliases.setdefault(target.id, set()).add(wrapped)

    def resolve(arg: ast.AST) -> List[str]:
        """Candidate function names a wrapper's first argument refers to."""
        wrapped = _partial_target(arg)
        if wrapped is not None:
            return [wrapped]
        name = dotted_name(arg)
        if name is None:
            return []
        last = name.rsplit(".", 1)[-1]
        return sorted(partial_aliases.get(last, set())) + [last]

    jitted: Dict[str, FuncDef] = {}
    for name, nodes in defs.items():
        for fn in nodes:
            if any(_is_jit_decorator(d) for d in fn.decorator_list):
                jitted[name] = fn
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        func = dotted_name(node.func)
        last = func.rsplit(".", 1)[-1] if func else None
        if func in _JIT_NAMES or last in _KERNEL_WRAPPER_LASTS:
            for kernel in resolve(node.args[0]):
                if kernel in defs:
                    jitted[kernel] = defs[kernel][0]
    return jitted


def _partial_target(node: ast.AST) -> Optional[str]:
    """``partial(fn, …)`` → ``fn``'s last name segment, else None."""
    if (
        isinstance(node, ast.Call)
        and dotted_name(node.func) in _PARTIAL_NAMES
        and node.args
    ):
        name = dotted_name(node.args[0])
        if name is not None:
            return name.rsplit(".", 1)[-1]
    return None


def _mutable_globals(tree: ast.Module) -> Set[str]:
    """Module names rebound at runtime: ``global`` targets + names with
    more than one module-level binding."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Global):
            out.update(node.names)
    counts: Dict[str, int] = {}
    for stmt in tree.body:
        targets: List[ast.AST] = []
        if isinstance(stmt, ast.Assign):
            targets = list(stmt.targets)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)) and stmt.value is not None:
            targets = [stmt.target]
        for t in targets:
            if isinstance(t, ast.Name):
                counts[t.id] = counts.get(t.id, 0) + 1
    out |= {name for name, n in counts.items() if n > 1}
    return out


def _local_bindings(fn: FuncDef) -> Set[str]:
    """Names bound inside the function (params, assignments, comps)."""
    bound: Set[str] = set()
    args = fn.args
    for a in list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs):
        bound.add(a.arg)
    if args.vararg:
        bound.add(args.vararg.arg)
    if args.kwarg:
        bound.add(args.kwarg.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, (ast.Store, ast.Del)):
            bound.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
            bound.add(node.name)
            for a in list(node.args.posonlyargs) + list(node.args.args) + list(node.args.kwonlyargs):
                bound.add(a.arg)
    return bound


class JitStalenessRule(Rule):
    id = "jit-staleness"
    summary = "no Settings/mutable-global reads or host syncs inside jitted bodies"

    def check_module(self, mod: SourceModule) -> Iterable[Finding]:
        jitted = _jitted_functions(mod.tree)
        if not jitted:
            return ()
        mutable = _mutable_globals(mod.tree)
        out: List[Finding] = []
        for name, fn in jitted.items():
            local = _local_bindings(fn)
            for node in ast.walk(fn):
                f = self._check_node(mod, name, node, mutable, local)
                if f is not None:
                    out.append(f)
        return out

    def _check_node(
        self,
        mod: SourceModule,
        fn_name: str,
        node: ast.AST,
        mutable: Set[str],
        local: Set[str],
    ) -> Optional[Finding]:
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "Settings"
        ):
            return self._finding(
                mod,
                node,
                fn_name,
                f"Settings.{node.attr} read inside jitted '{fn_name}' — the "
                "value is baked at first trace and goes stale (pass it as an "
                "argument or static_argname)",
            )
        if (
            isinstance(node, ast.Name)
            and isinstance(node.ctx, ast.Load)
            and node.id in mutable
            and node.id not in local
        ):
            return self._finding(
                mod,
                node,
                fn_name,
                f"mutable module global '{node.id}' read inside jitted "
                f"'{fn_name}' — it participates in no jit cache key (the "
                "BWD_MODE class); pass it as an explicit argument",
            )
        if isinstance(node, ast.Call):
            func = node.func
            if isinstance(func, ast.Attribute) and func.attr == "item" and not node.args:
                return self._finding(
                    mod,
                    node,
                    fn_name,
                    f".item() inside jitted '{fn_name}' — host sync on a "
                    "traced value",
                )
            name = dotted_name(func)
            if name in _HOST_SYNC_CALLS:
                return self._finding(
                    mod,
                    node,
                    fn_name,
                    f"{name}(…) inside jitted '{fn_name}' — host "
                    "materialization of a traced value",
                )
            if (
                isinstance(func, ast.Name)
                and func.id == "float"
                and node.args
                and not isinstance(node.args[0], ast.Constant)
            ):
                return self._finding(
                    mod,
                    node,
                    fn_name,
                    f"float(…) on a non-constant inside jitted '{fn_name}' — "
                    "host sync on a traced value (use jnp dtypes/astype)",
                )
        return None

    def _finding(self, mod: SourceModule, node: ast.AST, fn_name: str, msg: str) -> Finding:
        line, col = node_pos(node)
        return Finding(
            rule=self.id,
            path=mod.path,
            line=line,
            col=col,
            message=msg,
            context=fn_name,
        )
