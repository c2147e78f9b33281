"""JAX tracing-hazard pass (family ``tracing``).

Finds the bug classes that only explode at trace time (or worse, silently
recompile every step) inside jitted/scanned code in ``models/``, ``ops/``,
``engine/``, and ``parallel/``:

* ``tracing.python-branch-on-traced`` — ``if``/``while`` on a traced
  value: a ``TracerBoolConversionError`` at runtime, or a silent
  recompile when the value sneaks in via ``static_argnames``;
* ``tracing.host-sync-in-jit`` — ``.item()`` / ``float()`` / ``int()`` /
  ``bool()`` / ``np.asarray()`` / ``jax.device_get`` applied to a traced
  value inside jitted code: a device round-trip per call, or a trace
  error;
* ``tracing.dynamic-shape-in-jit`` — a traced value used as a shape (or
  ``range()`` bound): every new value is a new compilation;
* ``tracing.jit-closes-over-mutable-global`` — a jitted function reading
  a module global that some function rebinds (``global X``): jit baked
  the value at first trace and will never see the update;
* ``tracing.deprecated-api`` — the deprecated/moved-API table (run on
  EVERY module): ``jax.shard_map`` / ``jax.experimental.shard_map`` /
  ``pltpu.CompilerParams`` outside ``utils/jax_compat.py`` (the one module
  that follows their renames), ``jax.tree_map`` family (removed upstream).

Traced contexts: functions decorated ``@jax.jit`` (bare or via
``partial``), functions wrapped ``jax.jit(f)``, and local functions passed
to ``lax.scan`` / ``while_loop`` / ``cond`` / ``switch`` / ``fori_loop``.
Static argnames are honored.  Heuristics lean PRECISE over complete:
``x is None`` tests, ``isinstance``, and ``.shape``/``.ndim``/``.dtype``/
``len()`` uses are static under jit and never flagged.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from lmrs_tpu.analysis.core import Finding, Module, RepoContext

_SCOPE_PREFIXES = ("lmrs_tpu/models/", "lmrs_tpu/ops/", "lmrs_tpu/engine/",
                   "lmrs_tpu/parallel/")

_LAX_HOFS = frozenset(("scan", "while_loop", "cond", "switch", "fori_loop",
                       "associative_scan", "map"))

# dotted-name -> (replacement hint).  The shim module itself is exempt.
_DEPRECATED = {
    "jax.shard_map": "use lmrs_tpu.utils.jax_compat.shard_map (one "
                     "place to follow the next rename)",
    "jax.experimental.shard_map": "import via lmrs_tpu.utils.jax_compat."
                                  "shard_map (the experimental home is "
                                  "gone from the installed jax)",
    "pltpu.CompilerParams": "use lmrs_tpu.utils.jax_compat."
                            "tpu_compiler_params (one place to follow "
                            "the next rename)",
    "jax.tree_map": "use jax.tree.map (removed from the jax namespace)",
    "jax.tree_multimap": "use jax.tree.map",
    "jax.tree_leaves": "use jax.tree.leaves",
}
_COMPAT_MODULE = "lmrs_tpu/utils/jax_compat.py"


def _dotted(node: ast.AST) -> str:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _static_argnames(call: ast.Call) -> set[str]:
    """Literal static_argnames from a jax.jit / partial(jax.jit, ...) call."""
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return {v.value}
            if isinstance(v, (ast.Tuple, ast.List)):
                return {el.value for el in v.elts
                        if isinstance(el, ast.Constant)
                        and isinstance(el.value, str)}
    return set()


def _is_jit_call(call: ast.Call) -> bool:
    name = _dotted(call.func)
    return name in ("jax.jit", "jit")


@dataclass
class _TracedFn:
    fn: ast.FunctionDef
    static: set[str]
    via: str  # "jit" | lax hof name


def _collect_traced(mod: Module) -> list[_TracedFn]:
    """Jitted / lax-traced function defs in a module."""
    out: list[_TracedFn] = []
    # local defs by name per enclosing scope, to resolve Name references
    defs: dict[str, ast.FunctionDef] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.FunctionDef):
            defs[node.name] = node

    claimed: set[ast.FunctionDef] = set()

    def claim(fn: ast.FunctionDef | None, static: set[str],
              via: str) -> None:
        if fn is not None and fn not in claimed:
            claimed.add(fn)
            out.append(_TracedFn(fn, static, via))

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    name = _dotted(dec.func)
                    if name in ("jax.jit", "jit"):
                        claim(node, _static_argnames(dec), "jit")
                    elif name.endswith("partial") and dec.args and \
                            isinstance(dec.args[0], (ast.Attribute,
                                                     ast.Name)) and \
                            _dotted(dec.args[0]) in ("jax.jit", "jit"):
                        claim(node, _static_argnames(dec), "jit")
                elif _dotted(dec) in ("jax.jit", "jit"):
                    claim(node, set(), "jit")
        elif isinstance(node, ast.Call):
            name = _dotted(node.func)
            if _is_jit_call(node) and node.args and \
                    isinstance(node.args[0], ast.Name):
                claim(defs.get(node.args[0].id), _static_argnames(node),
                      "jit")
            leaf = name.rsplit(".", 1)[-1]
            if leaf in _LAX_HOFS and (name.startswith("lax.")
                                      or name.startswith("jax.lax.")):
                for arg in node.args[:2]:
                    if isinstance(arg, ast.Name):
                        claim(defs.get(arg.id), set(), leaf)
    return out


def _taint(fn: ast.FunctionDef, static: set[str]) -> set[str]:
    """Parameter-derived (traced) names: params minus statics, propagated
    through simple assignments (two passes ~= fixpoint for linear code).
    Propagation uses DYNAMIC uses only — ``b, h = q.shape``,
    ``flag = x is None``, and ``n = len(xs)`` produce static Python
    values, not tracers."""
    tainted = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                               + fn.args.kwonlyargs)} - static - {"self"}
    for _ in range(2):
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                if _dynamic_names(node.value, tainted):
                    for t in node.targets:
                        for el in (t.elts if isinstance(
                                t, (ast.Tuple, ast.List)) else [t]):
                            if isinstance(el, ast.Name):
                                tainted.add(el.id)
    return tainted


def _dynamic_names(expr: ast.AST, tainted: set[str]) -> set[str]:
    """Tainted names used DYNAMICALLY in ``expr`` — shape/dtype/ndim/len
    reads, ``is None`` tests, and isinstance checks are static under jit
    and excluded."""
    static_spots: set[int] = set()

    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and \
                node.attr in ("shape", "ndim", "dtype", "size") and \
                isinstance(node.value, ast.Name):
            static_spots.add(id(node.value))
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Name) and \
                node.func.id in ("len", "isinstance", "getattr",
                                 "hasattr", "type"):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    static_spots.add(id(sub))
        elif isinstance(node, ast.Compare) and \
                all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in node.ops):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    static_spots.add(id(sub))
    return {n.id for n in ast.walk(expr)
            if isinstance(n, ast.Name) and n.id in tainted
            and id(n) not in static_spots}


_SHAPE_MAKERS = frozenset(("zeros", "ones", "full", "empty", "arange",
                           "broadcast_to", "iota"))
_HOST_SYNC_FNS = frozenset(("float", "int", "bool"))


def _mutable_globals(mod: Module) -> set[str]:
    """Module globals some function rebinds via ``global X; X = ...``."""
    out: set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Global):
            out.update(node.names)
    return out


def _check_traced_fn(mod: Module, tf: _TracedFn,
                     mutable_globals: set[str],
                     findings: list[Finding]) -> None:
    tainted = _taint(tf.fn, tf.static)
    local_names = set(tainted)
    for node in ast.walk(tf.fn):
        if isinstance(node, (ast.If, ast.While)):
            dyn = _dynamic_names(node.test, tainted)
            if dyn:
                findings.append(Finding(
                    rule="tracing.python-branch-on-traced",
                    path=mod.path, line=node.lineno,
                    message=f"Python `{'while' if isinstance(node, ast.While) else 'if'}` "
                            f"on traced value(s) {', '.join(sorted(dyn))} "
                            f"inside {tf.via}-traced `{tf.fn.name}`",
                    hint="use jnp.where / lax.cond / lax.select, or move "
                         "the branch out of the traced function (mark the "
                         "argument static if it truly is)"))
        elif isinstance(node, ast.Call):
            name = _dotted(node.func)
            leaf = name.rsplit(".", 1)[-1]
            arg_dyn = set()
            for arg in node.args:
                arg_dyn |= _dynamic_names(arg, tainted)
            if leaf == "item" and isinstance(node.func, ast.Attribute):
                base_dyn = _dynamic_names(node.func.value, tainted)
                if base_dyn:
                    findings.append(Finding(
                        rule="tracing.host-sync-in-jit",
                        path=mod.path, line=node.lineno,
                        message=f".item() on traced value inside "
                                f"{tf.via}-traced `{tf.fn.name}`",
                        hint="keep the value on device (jnp ops), or "
                             "return it and sync outside the jit"))
            elif (name in _HOST_SYNC_FNS or name in ("np.asarray",
                                                     "np.array",
                                                     "numpy.asarray",
                                                     "jax.device_get")) \
                    and arg_dyn:
                findings.append(Finding(
                    rule="tracing.host-sync-in-jit",
                    path=mod.path, line=node.lineno,
                    message=f"{name}() forces a host sync on traced "
                            f"value(s) {', '.join(sorted(arg_dyn))} inside "
                            f"{tf.via}-traced `{tf.fn.name}`",
                    hint="jnp equivalents stay on device; host conversion "
                         "belongs outside the traced function"))
            elif leaf in _SHAPE_MAKERS and node.args:
                # broadcast_to(arr, shape): the shape is the SECOND arg
                idx = 1 if leaf == "broadcast_to" else 0
                if len(node.args) <= idx:
                    continue
                shape_arg = node.args[idx]
                dyn = _dynamic_names(shape_arg, tainted)
                if dyn:
                    findings.append(Finding(
                        rule="tracing.dynamic-shape-in-jit",
                        path=mod.path, line=node.lineno,
                        message=f"traced value(s) {', '.join(sorted(dyn))} "
                                f"used as a shape in {leaf}() inside "
                                f"{tf.via}-traced `{tf.fn.name}`",
                        hint="shapes must be Python ints under jit — pad "
                             "to a bucket or hoist the shape computation; "
                             "every distinct value recompiles"))
            elif name == "range" and arg_dyn:
                findings.append(Finding(
                    rule="tracing.dynamic-shape-in-jit",
                    path=mod.path, line=node.lineno,
                    message=f"range() over traced value(s) "
                            f"{', '.join(sorted(arg_dyn))} inside "
                            f"{tf.via}-traced `{tf.fn.name}`",
                    hint="use lax.fori_loop / lax.scan for traced trip "
                         "counts"))
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if node.id in mutable_globals and node.id not in local_names:
                findings.append(Finding(
                    rule="tracing.jit-closes-over-mutable-global",
                    path=mod.path, line=node.lineno,
                    message=f"{tf.via}-traced `{tf.fn.name}` reads module "
                            f"global {node.id}, which is rebound elsewhere "
                            "(`global` statement): jit baked the first-"
                            "trace value",
                    hint="pass the value as an argument (static or "
                         "traced) instead of closing over it"))


def _check_deprecated(mod: Module, findings: list[Finding]) -> None:
    if mod.path == _COMPAT_MODULE:
        return
    for node in ast.walk(mod.tree):
        name = None
        line = getattr(node, "lineno", 1)
        if isinstance(node, ast.Attribute):
            name = _dotted(node)
        elif isinstance(node, ast.ImportFrom) and node.module:
            name = node.module
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name in _DEPRECATED:
                    name = alias.name
        if name in _DEPRECATED:
            findings.append(Finding(
                rule="tracing.deprecated-api",
                path=mod.path, line=line,
                message=f"deprecated/moved JAX API `{name}`",
                hint=_DEPRECATED[name]))


def run(ctx: RepoContext) -> list[Finding]:
    findings: list[Finding] = []
    for mod in ctx.modules:
        _check_deprecated(mod, findings)
        if not (mod.path.startswith(_SCOPE_PREFIXES)
                or mod.path.startswith("fixtures/")):
            continue
        mg = _mutable_globals(mod)
        for tf in _collect_traced(mod):
            _check_traced_fn(mod, tf, mg, findings)
    return findings
