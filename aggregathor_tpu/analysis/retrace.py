"""Retrace / host-sync lint: the zero-recompile discipline, statically.

The engines' contract (docs/engine.md) is ONE steady-state executable per
step shape — every compile after warmup is a regression the flight
recorder's ``CompileWatch`` only catches at the configs a run happens to
exercise.  This checker flags the four mistake shapes that break the
discipline anywhere in the package:

- **RT001** — a ``jax.jit``/``pjit``/``pmap`` wrapper constructed inside a
  loop body or inside traced code: a fresh jit object has a fresh cache, so
  every call recompiles.
- **RT002** — host synchronisation on a traced value inside a traced scope:
  ``.item()`` / ``float()`` / ``int()`` / ``bool()`` / ``np.asarray()`` /
  ``np.array()`` / ``jax.device_get()`` force a device round-trip (or a
  ``ConcretizationTypeError``) in the middle of the graph.
- **RT003** — a Python ``if``/``while`` on a traced value: the branch is
  resolved at TRACE time, so each taken arm bakes a different program
  (retrace per boolean) or fails to trace outright.
- **RT004** — ``static_argnums``/``static_argnames`` naming a parameter
  whose default is a mutable literal (list/dict/set): unhashable statics
  fail at call time, and even a hashable wrapper defeats cache hits.

**Traced scopes** are found syntactically: a function is traced when it is
decorated with (or passed by name to) one of the JAX tracing wrappers
(``jit``/``pjit``/``pmap``/``vmap``/``grad``/``value_and_grad``/
``shard_map``/``scan``/``cond``/``while_loop``/``fori_loop``/``switch``/
``remat``/``checkpoint``/``custom_vjp``), including through one assignment
alias (``sharded = shard_map(body, ...); jax.jit(sharded)`` — the engine
idiom), plus everything lexically nested in, or intra-module-reachable
from, a traced function.  **Traced values** are the traced function's
parameters and anything assigned from an expression that reads one;
``.shape``/``.ndim``/``.dtype``/``len()``/``isinstance()``/``is None``
projections are static and never flagged.

This is a conservative approximation: closure variables are treated as
static (they are, w.r.t. tracing), unresolvable aliases are skipped, and a
value smuggled through a container is invisible.  The checker proves the
absence of the *patterns*, the compile-count tests prove the end-to-end
property at the sampled configs — both, on every PR (docs/analysis.md).
"""

import ast

from .core import (
    Finding,
    callee_name,
    callee_tail,
    dotted_name,
    enclosing_function,
    reachable_functions,
)

CHECKER = "retrace"

#: callables whose function argument becomes traced code
TRACING_WRAPPERS = frozenset({
    "jit", "pjit", "pmap", "vmap", "grad", "value_and_grad", "shard_map",
    "scan", "cond", "while_loop", "fori_loop", "switch", "remat",
    "checkpoint", "custom_vjp", "custom_jvp", "eval_shape", "make_jaxpr",
})

#: wrappers that create a fresh compilation cache (RT001 when per-call)
JIT_WRAPPERS = frozenset({"jit", "pjit", "pmap"})

#: attribute projections of a traced array that are static at trace time
STATIC_ATTRS = frozenset({"shape", "ndim", "dtype", "size", "sharding", "aval"})

#: calls whose result on a traced argument is static at trace time
STATIC_CALLS = frozenset({"len", "isinstance", "type", "id", "repr", "getattr", "hasattr"})

HOST_SYNC_BUILTINS = frozenset({"float", "int", "bool", "complex"})
HOST_SYNC_NUMPY = frozenset({"asarray", "array", "copy", "ascontiguousarray"})
NUMPY_ROOTS = frozenset({"np", "numpy", "onp"})


def _decorator_traces(dec):
    """True when a decorator expression invokes a tracing wrapper."""
    if isinstance(dec, ast.Call):
        tail = callee_tail(dec)
        if tail == "partial":
            return any(_tail_of(arg) in TRACING_WRAPPERS for arg in dec.args)
        return tail in TRACING_WRAPPERS
    return _tail_of(dec) in TRACING_WRAPPERS


def _tail_of(node):
    name = dotted_name(node)
    return name.rsplit(".", 1)[-1] if name else None


def _functions_by_name_in_scope(module):
    """Map function name -> def nodes (module-level and nested)."""
    table = {}
    for func in module.functions():
        table.setdefault(func.name, []).append(func)
    return table


def find_traced_functions(module):
    """The set of function defs that execute under a JAX trace."""
    by_name = _functions_by_name_in_scope(module)
    traced = []

    def mark(func):
        if func is not None and not any(func is f for f in traced):
            traced.append(func)

    # pass 1: decorators
    for func in module.functions():
        if any(_decorator_traces(dec) for dec in func.decorator_list):
            mark(func)

    # pass 2: names passed to tracing wrappers, through one alias hop
    # (``sharded = shard_map(body, ...)`` then ``jax.jit(sharded)`` marks
    # ``body`` via the shard_map call directly)
    for node in ast.walk(module.tree):
        if not (isinstance(node, ast.Call) and callee_tail(node) in TRACING_WRAPPERS):
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name):
                caller = enclosing_function(module, node)
                # prefer a def in the same lexical function, else module level
                candidates = by_name.get(arg.id, [])
                chosen = None
                for cand in candidates:
                    if caller is not None and enclosing_function(module, cand) is caller:
                        chosen = cand
                        break
                if chosen is None and candidates:
                    chosen = candidates[0]
                mark(chosen)
            elif isinstance(arg, ast.Lambda):
                pass  # lambdas handled below via containment in traced scopes

    # pass 3: lexical nesting — a def inside a traced def is traced
    changed = True
    while changed:
        changed = False
        for func in module.functions():
            if any(func is f for f in traced):
                continue
            parent = enclosing_function(module, func)
            while parent is not None:
                if any(parent is f for f in traced):
                    mark(func)
                    changed = True
                    break
                parent = enclosing_function(module, parent)

    # pass 4: intra-module reachability — helpers CALLED from traced code
    # run under the same trace (the engine body calling _finalize_step)
    return reachable_functions(module, traced)


# --------------------------------------------------------------------- #
# Traced-value dataflow inside one traced function


def _names_in(node):
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _assigned_names(target):
    return {n.id for n in ast.walk(target) if isinstance(n, ast.Name)
            and isinstance(n.ctx, (ast.Store,))}


#: parameter names that are static-by-convention inside traced code: mesh
#: axis NAMES (strings, the shard_map API), config records (hashable
#: statics), and the trace machinery itself
STATIC_PARAM_NAMES = frozenset({"self", "cls", "cfg", "config", "axis", "axis_name"})


def traced_names(func):
    """Parameter-derived names inside ``func`` (forward propagation in
    statement order through :func:`is_dynamic` — a name assigned from a
    static projection like ``n, d = x.shape`` stays static; no kill —
    once traced, always suspect)."""
    args = func.args
    names = {
        a.arg
        for a in list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
        if a.arg not in STATIC_PARAM_NAMES and not a.arg.endswith("_axis")
    }
    for extra in (args.vararg, args.kwarg):
        if extra is not None:
            names.add(extra.arg)
    changed = True
    while changed:
        changed = False
        for node in ast.walk(func):
            value = None
            targets = []
            if isinstance(node, ast.Assign):
                value, targets = node.value, node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                value, targets = node.value, [node.target]
            elif isinstance(node, ast.For):
                value, targets = node.iter, [node.target]
            elif isinstance(node, (ast.NamedExpr,)):
                value, targets = node.value, [node.target]
            if value is None:
                continue
            if is_dynamic(value, names):
                for target in targets:
                    new = _assigned_names(target) - names
                    if new:
                        names |= new
                        changed = True
    return names


def is_dynamic(expr, traced):
    """True when ``expr`` reads a traced name OUTSIDE a static projection."""

    def walk(node):
        if isinstance(node, ast.Name):
            return node.id in traced
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return False
            return walk(node.value)
        if isinstance(node, ast.Call):
            tail = callee_tail(node)
            if tail in STATIC_CALLS:
                return False
            return any(walk(child) for child in list(node.args)
                       + [kw.value for kw in node.keywords]) or walk(node.func)
        if isinstance(node, ast.Compare):
            # ``x is None`` / ``x is not None`` is a static config check
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return any(walk(c) for c in [node.left] + node.comparators)
        if isinstance(node, ast.Subscript):
            return walk(node.value) or walk(node.slice)
        return any(walk(child) for child in ast.iter_child_nodes(node))

    return walk(expr)


def _in_loop(module, node, stop_at):
    """True when ``node`` sits inside a for/while loop body below ``stop_at``."""
    cur = module.parent(node)
    while cur is not None and cur is not stop_at:
        if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
            return True
        cur = module.parent(cur)
    return False


def _static_params(call, target_def):
    """Parameter names declared static by a jit call, resolved on the
    jitted function's signature.  Returns [] when unresolvable."""
    if target_def is None:
        return []
    params = [a.arg for a in target_def.args.args]
    names = []
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    names.append(el.value)
        elif kw.arg == "static_argnums":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, int):
                    if 0 <= el.value < len(params):
                        names.append(params[el.value])
    return names


def check_module(module):
    findings = []
    traced_funcs = find_traced_functions(module)
    by_name = _functions_by_name_in_scope(module)

    # RT001 / RT004: every jit-wrapper construction site in the module
    for node in ast.walk(module.tree):
        if not (isinstance(node, ast.Call) and callee_tail(node) in JIT_WRAPPERS):
            continue
        name = callee_name(node) or ""
        if not (name in JIT_WRAPPERS or name.startswith("jax.")):
            continue  # someone else's jit/pmap attribute
        func = enclosing_function(module, node)
        scope = module.qualname(func) if func is not None else ""
        if _in_loop(module, node, func):
            findings.append(Finding(
                CHECKER, "RT001", module.path, node.lineno, scope, name,
                "%s(...) constructed inside a loop body: a fresh wrapper has "
                "a fresh compile cache, every iteration recompiles — build "
                "once outside the loop" % name,
            ))
        if func is not None and any(func is f for f in traced_funcs):
            findings.append(Finding(
                CHECKER, "RT001", module.path, node.lineno, scope, name + ".traced",
                "%s(...) constructed inside traced code: the wrapper is "
                "rebuilt on every trace — hoist it to build time" % name,
            ))
        # RT004: static params with mutable literal defaults
        target = None
        if node.args and isinstance(node.args[0], ast.Name):
            for cand in by_name.get(node.args[0].id, []):
                target = cand
                break
        statics = _static_params(node, target)
        if statics and target is not None:
            defaults = target.args.defaults
            params = [a.arg for a in target.args.args]
            offset = len(params) - len(defaults)
            for i, default in enumerate(defaults):
                pname = params[offset + i]
                if pname in statics and isinstance(
                    default, (ast.List, ast.Dict, ast.Set)
                ):
                    findings.append(Finding(
                        CHECKER, "RT004", module.path, node.lineno,
                        module.qualname(target), pname,
                        "static argument %r of %r defaults to a mutable "
                        "(unhashable) literal: jit statics must be hashable "
                        "or every call fails/recompiles" % (pname, target.name),
                    ))

    # RT002 / RT003: inside each traced function
    for func in traced_funcs:
        traced = traced_names(func)
        scope = module.qualname(func)

        def owned(node, func=func):
            """Node belongs to this func, not a nested def (checked itself)."""
            cur = enclosing_function(module, node)
            return cur is func

        for node in ast.walk(func):
            if not owned(node):
                continue
            if isinstance(node, ast.Call):
                tail = callee_tail(node)
                name = callee_name(node) or ""
                root = name.split(".", 1)[0]
                args = list(node.args) + [kw.value for kw in node.keywords]
                dynamic_arg = any(is_dynamic(a, traced) for a in args)
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and not node.args
                    and is_dynamic(node.func.value, traced)
                ):
                    findings.append(Finding(
                        CHECKER, "RT002", module.path, node.lineno, scope, "item",
                        ".item() on a traced value inside traced code forces "
                        "a host sync (or a ConcretizationTypeError)",
                    ))
                elif tail in HOST_SYNC_BUILTINS and name == tail and dynamic_arg:
                    findings.append(Finding(
                        CHECKER, "RT002", module.path, node.lineno, scope, tail,
                        "%s() on a traced value inside traced code "
                        "concretizes the tracer on the host" % tail,
                    ))
                elif root in NUMPY_ROOTS and tail in HOST_SYNC_NUMPY and dynamic_arg:
                    findings.append(Finding(
                        CHECKER, "RT002", module.path, node.lineno, scope, name,
                        "%s() on a traced value pulls the array to the host "
                        "mid-graph — use jnp inside traced code" % name,
                    ))
                elif name.endswith("device_get") and dynamic_arg:
                    findings.append(Finding(
                        CHECKER, "RT002", module.path, node.lineno, scope, name,
                        "device_get inside traced code is a host round-trip "
                        "per trace",
                    ))
            elif isinstance(node, (ast.If, ast.While)):
                if is_dynamic(node.test, traced):
                    culprits = sorted(_names_in(node.test) & traced)
                    findings.append(Finding(
                        CHECKER, "RT003", module.path, node.lineno, scope,
                        ",".join(culprits) or "test",
                        "Python %s on a traced value: the branch is resolved "
                        "at trace time (retrace per boolean) — use "
                        "jnp.where/lax.cond" % (
                            "while" if isinstance(node, ast.While) else "if",
                        ),
                    ))
    return findings


def check(modules):
    findings = []
    for module in modules:
        findings.extend(check_module(module))
    return findings
