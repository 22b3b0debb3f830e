"""J-series rules: the jax drift/tracing invariants this repo learned the
hard way. Each rule's docstring names the incident it encodes; the catalog
with reproduction context lives in ``docs/static_analysis.md``.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator

from predictionio_tpu.analysis.astutil import (
    call_name,
    const_strings,
    dotted,
    func_defs,
    keyword,
    walk_calls,
)
from predictionio_tpu.analysis.engine import Finding, ModuleContext

#: the one module allowed to touch the drifting jax surface directly
SHIM_PATH_SUFFIX = "utils/jax_compat.py"

JIT_NAMES = {"jax.jit", "jit", "jax.pjit", "pjit"}
PARTIAL_NAMES = {"functools.partial", "partial"}

_OPT_STATE_RE = re.compile(r"opt_state|optimizer|adam_state", re.IGNORECASE)

#: names whose presence marks a module as doing sharded placement (the
#: precondition under which legacy-jax donation of optimizer state
#: miscompiles -- an unsharded trainer donating moments is fine)
_SHARDING_MARKERS = {
    "NamedSharding", "put_global", "shard_map", "with_sharding_constraint",
    "PartitionSpec",
}


def _is_shim(ctx: ModuleContext) -> bool:
    return ctx.path.endswith(SHIM_PATH_SUFFIX)


def _jit_index(ctx: ModuleContext) -> "_JitIndex":
    """One _JitIndex per module, shared by J003/J004. Cached on the
    context object itself (the symbols map builds lazily and must stay
    pure node->qualname)."""
    cached = getattr(ctx, "_jit_index_cache", None)
    if cached is None:
        cached = _JitIndex(ctx)
        ctx._jit_index_cache = cached
    return cached


class _JitIndex:
    """Functions that run under trace: ``@jax.jit``-style decorations,
    ``jax.jit(fn, ...)`` call sites (including one level of factory
    resolution: ``jax.jit(make_step(...))`` -> the nested def ``make_step``
    returns), and Pallas kernel bodies (first arg of ``pallas_call``)."""

    def __init__(self, ctx: ModuleContext):
        self.defs = func_defs(ctx.tree)
        #: id(FunctionDef) -> set of static (untraced) parameter names
        self.jitted: dict[int, tuple[ast.FunctionDef, set[str]]] = {}
        self.kernels: dict[int, ast.FunctionDef] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                jit_call = self._jit_decorator(node)
                if jit_call is not None:
                    self._mark(node, jit_call)
            elif isinstance(node, ast.Call):
                name = call_name(node)
                if name in JIT_NAMES and node.args:
                    for fn in self._resolve_fn(node.args[0]):
                        self._mark(fn, node)
                elif name.endswith("pallas_call") and node.args:
                    target = node.args[0]
                    if isinstance(target, ast.Name):
                        for fn in self.defs.get(target.id, []):
                            self.kernels[id(fn)] = fn

    def _jit_decorator(self, node: ast.FunctionDef) -> ast.Call | None:
        for dec in node.decorator_list:
            if (dotted(dec) or "") in JIT_NAMES:
                return ast.Call(func=dec, args=[], keywords=[])
            if isinstance(dec, ast.Call):
                name = call_name(dec)
                if name in JIT_NAMES:
                    return dec
                if name in PARTIAL_NAMES and dec.args and (
                    dotted(dec.args[0]) or ""
                ) in JIT_NAMES:
                    return dec
        return None

    def _resolve_fn(self, arg: ast.AST) -> list[ast.FunctionDef]:
        """``jax.jit(X)``: X a local def, or a call to a factory whose
        ``return <name>`` names a nested def (the make_train_step shape)."""
        if isinstance(arg, ast.Name):
            return self.defs.get(arg.id, [])
        if isinstance(arg, ast.Call) and isinstance(arg.func, ast.Name):
            out = []
            for factory in self.defs.get(arg.func.id, []):
                for ret in ast.walk(factory):
                    if isinstance(ret, ast.Return) and isinstance(ret.value, ast.Name):
                        for inner in self.defs.get(ret.value.id, []):
                            # the nested def, not a same-named global
                            if any(inner is n for n in ast.walk(factory)):
                                out.append(inner)
            return out
        return []

    def _mark(self, fn: ast.FunctionDef, jit_call: ast.Call) -> None:
        static: set[str] = set()
        params = _param_names(fn)
        kw = keyword(jit_call, "static_argnames")
        if kw is not None:
            static |= const_strings(kw.value)
        kw = keyword(jit_call, "static_argnums")
        if kw is not None:
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, int):
                    if 0 <= c.value < len(params):
                        static.add(params[c.value])
        self.jitted[id(fn)] = (fn, static)


def _param_names(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in a.posonlyargs + a.args]


def _tainted_names(fn: ast.FunctionDef, static: set[str]) -> set[str]:
    """Names bound to (potentially) traced values inside a jitted scope:
    the parameters, plus anything assigned from jnp/lax math on them."""
    tainted = {
        p.arg
        for p in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
        if p.arg not in static and p.arg != "self"
    }
    for _ in range(4):  # small fixpoint; chains in practice are short
        grew = False
        for node in ast.walk(fn):
            targets: list[ast.AST] = []
            if isinstance(node, ast.Assign):
                value, targets = node.value, node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                value, targets = node.value, [node.target]
            else:
                continue
            if value is None or not _expr_tainted(value, tainted):
                continue
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name) and n.id not in tainted:
                        tainted.add(n.id)
                        grew = True
        if not grew:
            break
    return tainted


def _expr_tainted(expr: ast.AST, tainted: set[str]) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Call):
            d = call_name(n)
            if d.startswith(("jnp.", "jax.numpy.", "jax.lax.")):
                return True
        if isinstance(n, ast.Name) and n.id in tainted:
            return True
    return False


#: calls whose result is static even when the argument is traced
_STATIC_CALLS = {"len", "isinstance", "hasattr", "callable", "getattr", "type"}
#: attributes that are static python values on tracers (branching on a
#: shape or dtype is legitimate trace-time specialization)
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}


def _test_tainted(test: ast.AST, tainted: set[str]) -> bool:
    """Taint check for branch tests, pruning subexpressions that are
    STATIC at trace time even on traced values: ``len(x)``, ``x.shape``,
    ``x is None`` identity checks, isinstance/hasattr."""
    if isinstance(test, ast.Call):
        name = call_name(test)
        if name in _STATIC_CALLS:
            return False
        if name.startswith(("jnp.", "jax.numpy.", "jax.lax.")):
            return True
    if isinstance(test, ast.Attribute) and test.attr in _STATIC_ATTRS:
        return False
    if isinstance(test, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops
    ):
        return False
    if isinstance(test, ast.Name):
        return test.id in tainted
    return any(_test_tainted(c, tainted) for c in ast.iter_child_nodes(test))


class RuleJ001:
    """Direct ``jax.experimental`` / ``jax.shard_map`` / ``pjit`` use outside
    the drift shim. Incident: jax 0.4.37 renamed/moved this entire surface
    (``check_vma`` vs ``check_rep``, ``jax.shard_map`` vs
    ``jax.experimental.shard_map``); every direct import is a copy of the
    drift policy that rots independently. Route through utils/jax_compat."""

    rule_id = "J001"
    severity = "error"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if _is_shim(ctx):
            return
        seen: set[int] = set()

        def finding(node: ast.AST, what: str) -> Finding | None:
            if node.lineno in seen:
                return None
            seen.add(node.lineno)
            return Finding(
                self.rule_id, self.severity, ctx.path, node.lineno,
                ctx.symbol_for(node),
                f"direct {what} outside utils/jax_compat (drift-shim policy)",
                "import the equivalent name from predictionio_tpu.utils.jax_compat",
            )

        for node in ast.walk(ctx.tree):
            f = None
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.startswith("jax.experimental"):
                        f = finding(node, f"import of {alias.name}")
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod.startswith("jax.experimental"):
                    f = finding(node, f"import from {mod}")
                elif mod == "jax" and any(
                    a.name in ("shard_map", "pjit") for a in node.names
                ):
                    f = finding(node, "import of jax.shard_map/pjit")
            elif isinstance(node, ast.Attribute):
                d = dotted(node) or ""
                if d.startswith("jax.experimental") or d in (
                    "jax.shard_map", "jax.pjit",
                ):
                    f = finding(node, f"use of {d}")
            if f is not None:
                yield f


class RuleJ003:
    """Python ``if``/``while``/``assert`` on a ``jnp``-derived value
    inside a ``@jit`` scope or Pallas kernel (static tests -- ``x is
    None``, ``len()``, ``.shape`` -- are pruned); use
    lax.cond/select/while_loop instead.

    Incident: TracerBoolConversionError at trace time at best, silent
    specialization on a trace-time constant at worst -- the bug class
    every template trainer hit at least once before the gate existed."""

    rule_id = "J003"
    severity = "error"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        index = _jit_index(ctx)
        scopes = [(fn, static) for fn, static in index.jitted.values()]
        scopes += [(fn, set()) for fn in index.kernels.values()]
        reported: set[int] = set()
        for fn, static in scopes:
            tainted = _tainted_names(fn, static)
            for node in ast.walk(fn):
                if not isinstance(node, (ast.If, ast.While, ast.Assert)):
                    continue
                if node.lineno in reported:
                    continue
                if _test_tainted(node.test, tainted):
                    reported.add(node.lineno)
                    kind = type(node).__name__.lower()
                    yield Finding(
                        self.rule_id, self.severity, ctx.path, node.lineno,
                        ctx.symbol_for(node),
                        f"python `{kind}` on a traced value inside jitted "
                        f"scope {fn.name!r}",
                        "use jax.lax.cond / jnp.where / lax.while_loop, or "
                        "hoist the branch out of the jitted function",
                    )


class RuleJ004:
    """Host-sync calls (``.item()``, ``float()``/``int()``/``bool()``,
    ``np.asarray``) on traced values inside jit: they either fail at
    trace time or silently force a device->host transfer per call on the
    serving hot path.

    Incident: the NCF serving path once re-uploaded its operands and
    dispatched eagerly, with a host sync, on every query."""

    rule_id = "J004"
    severity = "warning"

    _CASTS = {"float", "int", "bool"}
    _NP_SINKS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        index = _jit_index(ctx)
        scopes = [(fn, static) for fn, static in index.jitted.values()]
        scopes += [(fn, set()) for fn in index.kernels.values()]
        reported: set[int] = set()
        for fn, static in scopes:
            tainted = _tainted_names(fn, static)
            for call in walk_calls(fn):
                if call.lineno in reported:
                    continue
                what = self._host_sync(call, tainted)
                if what is None:
                    continue
                reported.add(call.lineno)
                yield Finding(
                    self.rule_id, self.severity, ctx.path, call.lineno,
                    ctx.symbol_for(call),
                    f"host-sync `{what}` on a traced value inside jitted "
                    f"scope {fn.name!r}",
                    "keep values on device inside jit; convert on the host "
                    "after the jitted call returns",
                )

    def _host_sync(self, call: ast.Call, tainted: set[str]) -> str | None:
        if (
            isinstance(call.func, ast.Attribute)
            and call.func.attr == "item"
            and not call.args
            and _test_tainted(call.func.value, tainted)
        ):
            return ".item()"
        name = call_name(call)
        if name in self._CASTS and len(call.args) == 1 and _test_tainted(
            call.args[0], tainted
        ):
            return f"{name}()"
        if name in self._NP_SINKS and call.args and _test_tainted(
            call.args[0], tainted
        ):
            return f"{name}()"
        return None


class RuleJ005:
    """Concat-then-reshard to a ``P(..., "model", ...)`` spec. Incident
    (PR 4): jax 0.4.37 GSPMD MISCOMPILES concatenating per-bucket outputs
    and resharding the result to the model axis -- values land in wrong
    rows. Assemble with dynamic_update_slice into a pre-sharded buffer and
    reshard single arrays only."""

    rule_id = "J005"
    severity = "error"

    _CONCAT = ("jnp.concatenate", "jnp.concat", "jax.numpy.concatenate",
               "jnp.vstack", "jnp.hstack")
    _RESHARD = ("jax.device_put", "device_put", "jax.lax.with_sharding_constraint",
                "lax.with_sharding_constraint", "with_sharding_constraint",
                "reshard", "jax.device_put_sharded")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        concat_names = self._concat_names(ctx.tree)
        model_spec_names = self._model_spec_names(ctx.tree)
        for call in walk_calls(ctx.tree):
            if call_name(call) not in self._RESHARD:
                continue
            args = list(call.args) + [kw.value for kw in call.keywords]
            has_concat = any(self._is_concat_value(a, concat_names) for a in args)
            if not has_concat:
                continue
            if not any(
                self._mentions_model_spec(a, model_spec_names) for a in args
            ):
                continue
            yield Finding(
                self.rule_id, self.severity, ctx.path, call.lineno,
                ctx.symbol_for(call),
                "concatenated array resharded to a P(...'model'...) spec "
                "(jax 0.4.37 GSPMD miscompile shape: values land in wrong "
                "rows)",
                "dynamic_update_slice each piece into a buffer already "
                "sharded on 'model'; only reshard single arrays",
            )

    def _concat_names(self, tree: ast.AST) -> set[str]:
        out: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and self._has_concat(node.value):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            out.add(n.id)
        return out

    def _has_concat(self, expr: ast.AST) -> bool:
        return any(
            call_name(c) in self._CONCAT for c in walk_calls(expr)
        )

    def _is_concat_value(self, expr: ast.AST, concat_names: set[str]) -> bool:
        if self._has_concat(expr):
            return True
        return isinstance(expr, ast.Name) and expr.id in concat_names

    def _model_spec_names(self, tree: ast.AST) -> set[str]:
        out: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and self._spec_in(node.value):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            out.add(n.id)
        return out

    def _spec_in(self, expr: ast.AST) -> bool:
        for c in walk_calls(expr):
            name = call_name(c)
            if name.split(".")[-1] in ("P", "PartitionSpec", "NamedSharding"):
                if "model" in const_strings(c):
                    return True
        return False

    def _mentions_model_spec(self, expr: ast.AST, spec_names: set[str]) -> bool:
        if self._spec_in(expr):
            return True
        return any(
            isinstance(n, ast.Name) and n.id in spec_names
            for n in ast.walk(expr)
        )


class RuleJ006:
    """Loop-invariant host->device transfer inside a training loop.
    Incident (PR 10, device-resident epochs): ``fold_in_users`` re-shipped
    the FROZEN item-factor table to the device on every retrain cycle, and
    the first draft of the streamed ALS epoch loop would have re-shipped
    the opposite-side factor table / YtY Gram / ridge eye per block. A
    ``device_put``/``jnp.asarray``/``put_global`` whose argument the loop
    body never rebinds pays the host link (plus an allocation) once per
    iteration for bytes that never change -- hoist it above the loop (or
    cache the device copy, ``online.foldin._device_factors``). Per-batch
    transfers (the argument is sliced/rebound inside the loop) are the
    intended shape and stay silent, as do calls inside jitted scopes
    (tracers make them no-ops)."""

    rule_id = "J006"
    severity = "warning"

    _PUTS = {
        "jax.device_put", "device_put", "jnp.asarray", "jax.numpy.asarray",
        "put_global",
    }
    #: a loop is a TRAINING loop when its body calls something step-shaped;
    #: generic serving/IO loops stay out of scope. Deliberately NO
    #: `update`: `dict.update()`/`set.update()` in ordinary loops would
    #: misclassify them (optax-style `opt.update` loops call a step/fit
    #: function too, so coverage survives)
    _TRAIN_CALL_RE = re.compile(
        r"(^|[._])(step|iteration|train|fit|solve|fold)", re.IGNORECASE
    )

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        index = _jit_index(ctx)
        traced = {id(fn) for fn, _ in index.jitted.values()}
        traced |= set(index.kernels.keys())
        # one pass: every loop under a traced def (jitted / kernel) runs
        # on tracers, where the 'transfer' is a no-op
        traced_loops: set[int] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if id(node) in traced:
                    for n in ast.walk(node):
                        if isinstance(n, (ast.For, ast.While, ast.AsyncFor)):
                            traced_loops.add(id(n))
        reported: set[int] = set()
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While, ast.AsyncFor)):
                continue
            if id(loop) in traced_loops:
                continue
            if not self._is_training_loop(loop):
                continue
            bound = self._bound_names(loop)
            for call in walk_calls(loop):
                if call.lineno in reported:
                    continue
                name = call_name(call)
                if name not in self._PUTS or not call.args:
                    continue
                root = self._root_name(call.args[0])
                if root is None or root in bound:
                    continue
                reported.add(call.lineno)
                yield Finding(
                    self.rule_id, self.severity, ctx.path, call.lineno,
                    ctx.symbol_for(call),
                    f"`{name}({root}...)` inside a training loop, but "
                    f"{root!r} is never rebound in the loop body: a "
                    "loop-invariant host->device transfer per iteration",
                    "hoist the transfer above the loop (put once, reuse "
                    "the device array across iterations)",
                )

    def _is_training_loop(self, loop) -> bool:
        for call in walk_calls(loop):
            name = call_name(call)
            if name in self._PUTS:
                continue
            if self._TRAIN_CALL_RE.search(name or ""):
                return True
        return False

    def _bound_names(self, loop) -> set[str]:
        """Names (re)bound anywhere inside the loop, including its own
        targets: transfers of these are per-iteration by construction."""
        bound: set[str] = set()

        def add_target(t: ast.AST) -> None:
            for n in ast.walk(t):
                if isinstance(n, ast.Name):
                    bound.add(n.id)

        if isinstance(loop, (ast.For, ast.AsyncFor)):
            add_target(loop.target)
        for node in ast.walk(loop):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    add_target(t)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.NamedExpr)):
                add_target(node.target)
            elif isinstance(node, (ast.For, ast.AsyncFor)) and node is not loop:
                add_target(node.target)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if item.optional_vars is not None:
                        add_target(item.optional_vars)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                bound.add(node.name)
                for p in (node.args.posonlyargs + node.args.args
                          + node.args.kwonlyargs):
                    bound.add(p.arg)
        return bound

    #: wrappers to see through: device_put(np.asarray(x)) is still a
    #: transfer of x
    _UNWRAP = _PUTS | {"np.asarray", "numpy.asarray", "np.array",
                       "numpy.array"}

    def _root_name(self, expr: ast.AST) -> str | None:
        """The root variable of a bare Name / dotted attribute argument
        (seeing through asarray-style wrappers); subscripts and literals
        are per-iteration values and return None."""
        while (
            isinstance(expr, ast.Call)
            and call_name(expr) in self._UNWRAP
            and expr.args
        ):
            expr = expr.args[0]
        while isinstance(expr, ast.Attribute):
            expr = expr.value
        if isinstance(expr, ast.Name):
            return expr.id
        return None


RULES = (RuleJ001, RuleJ003, RuleJ004, RuleJ005, RuleJ006)
