"""Sharding-facts dataflow: the substrate the S-series rules interpret.

Every queued scale direction (the MPMD multi-engine slice scheduler,
multi-host streamed epochs, the sharded serving fabric) reshuffles mesh
construction, PartitionSpecs, and collectives across modules -- and this
repo's worst historical bugs live exactly there: the 0.4.37 GSPMD
concat->reshard miscompile, pallas_call being opaque to GSPMD outside
shard_map, and tp-sharded adam-state donation pairing the wrong buffers.
This module gives ``pio check`` eyes on that surface: an abstract
sharding-facts domain interpreted over PR 13's package call graph.

What it tracks, package-wide:

- **mesh construction sites**: ``Mesh(grid, ("data", "model"))`` literals
  (axis names read from the literal) and package mesh FACTORIES --
  functions like ``parallel/mesh.py``'s ``local_mesh`` whose every return
  is a mesh literal (or a call to an already-summarized factory), folded
  to a fixpoint so ``mesh = local_mesh(2, 2)`` binds axis names
  ``("data", "model")`` at the assignment;
- **PartitionSpec / NamedSharding literals**: ``P("model")`` /
  ``PartitionSpec("data", None)`` calls and the axis names they bind
  (the ``P = PartitionSpec`` alias resolves by last dotted component);
- **shard_map sites**: body (resolved through ``functools.partial``
  wrappers, local nested defs, and higher-order parameter bindings --
  the ``seq_parallel_shard_map(body, mesh, axis)`` forwarding shape),
  bound mesh, and in/out spec axis strings;
- **jit/pjit placement**: ``in_shardings``/``out_shardings``/
  ``donate_argnums``/``donate_argnames`` (callee parameter names resolved
  through the ``jit(make_step(...))`` factory form);
- **collectives**: ``psum`` / ``psum_scatter`` / ``all_gather`` /
  ``axis_index`` / ... with their STRING-LITERAL axis names (variable
  axis names are honestly unknown and stay out of the domain).

Values (mesh axes, spec axes) propagate interprocedurally: when a call
passes a known mesh or spec into a resolved callee, the callee's
parameter binds the value WITH the hand-off hop recorded, so a
``P("model")`` minted in one module and consumed three frames down in
another is joined against the mesh it actually lands
on, and the finding renders the mint->consume chain.

Execution contexts propagate the same way: each ``shard_map`` site seeds
its body with the site's axis environment (the resolved mesh's axis
names, or UNKNOWN -- an unknown environment binds everything, the
analysis errs quiet), and each jitted function seeds a "traced, no
enclosing shard_map" context; both flow down ordinary call edges with a
parent map kept per (function, seed) for witness-path reconstruction.
The join over paths is per-path, not a merge: a collective reached under
one environment that binds its axis and another that does not is a
finding on the second path, with that path as the witness.

``MeshFlow`` also renders ``pio check --mesh-report``: the complete
inventory of mesh / shard_map / PartitionSpec / NamedSharding / sharded-
jit construction sites (text + JSON) -- the worklist for extracting the
shared MPMD executor layer.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from predictionio_tpu.analysis.astutil import call_name, dotted, keyword

#: call-name LAST components that construct the things we track
_MESH_CTORS = {"Mesh"}
_SPEC_CTORS = {"P", "PartitionSpec"}
_NAMED_CTORS = {"NamedSharding"}
_JIT_LAST = {"jit", "pjit"}
#: collectives with an axis-name argument, mapped to the positional index
#: of that argument (keyword ``axis_name`` always wins)
_COLLECTIVES = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "psum_scatter": 1,
    "all_gather": 1, "all_to_all": 1, "ppermute": 1, "pbroadcast": 1,
    "axis_index": 0, "axis_size": 0, "pcast_varying": 1,
}
#: global-placement calls that are per-shard nonsense inside a shard_map
#: body (S005)
_GLOBAL_PLACEMENT = {
    "device_put", "device_put_sharded", "with_sharding_constraint",
    "put_global",
}

_MAX_FIXPOINT = 5
_MAX_TRAIL = 8


def _last(name: str) -> str:
    return name.rsplit(".", 1)[-1]


@dataclass(frozen=True)
class MeshVal:
    """A mesh value with statically-known axis names, plus its mint site
    and the hand-off trail it rode to wherever it is being read."""

    axes: tuple
    path: str
    qual: str
    line: int
    trail: tuple = ()

    @property
    def site(self) -> str:
        return f"{self.path}:{self.qual}:{self.line}"


@dataclass(frozen=True)
class SpecVal:
    """A PartitionSpec/NamedSharding value: the axis names it binds
    (``None`` entries dropped -- they name no axis), mint site, trail."""

    axes: tuple
    kind: str             # "PartitionSpec" | "NamedSharding"
    path: str
    qual: str
    line: int
    trail: tuple = ()

    @property
    def site(self) -> str:
        return f"{self.path}:{self.qual}:{self.line}"


@dataclass
class Site:
    """One inventory row of the mesh-report."""

    kind: str    # mesh | partition_spec | named_sharding | shard_map | sharded_jit
    path: str
    qual: str
    line: int
    detail: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.kind}] {self.qual}: {self.detail}"


@dataclass
class ShardMapSite:
    fi: object                  # FunctionInfo of the enclosing function
    line: int
    call: ast.Call
    bodies: list                # resolved body FunctionInfos
    mesh_vals: list             # MeshVal candidates for the mesh argument
    spec_axes: tuple            # axis-name strings appearing in in/out specs


@dataclass
class CollectiveSite:
    fi: object
    line: int
    op: str
    axes: tuple                 # string-literal axis names ((), if variable)


@dataclass
class DonatedCallable:
    """A jit-with-donation the enclosing scope can call by name."""

    name: str                   # the dotted callee name ("step", "self._step")
    jit_line: int
    positions: tuple            # donated positional indices into the CALL args


@dataclass
class Context:
    """One propagated execution context: how a function can be entered."""

    kind: str                   # "shard_map" | "jit"
    seed: str                   # "path:qual:line" of the site / jitted def
    axes: "tuple | None"        # bound axis names; None = unknown (binds all)
    mesh: "MeshVal | None" = None


class MeshFlow:
    """The shared sharding-facts layer: built once per PackageIndex, read
    by every S rule and by ``--mesh-report``."""

    def __init__(self, index):
        self.index = index
        self.graph = index.graph
        #: fkey -> axis tuple for mesh-factory functions
        self.factory_axes: dict = {}
        #: path -> {name: set[val]} module-level constants
        self.module_consts: dict = {}
        #: fkey -> {name: set[val]} local value environments
        self.fn_env: dict = {}
        #: (fkey, param) -> set[val] interprocedural bindings
        self.param_vals: dict = {}
        #: (path, clsqual, attr) -> set[val]
        self.attr_vals: dict = {}
        self.sites: list = []                    # inventory rows
        self.shardmap_sites: list = []
        #: fkey -> list[CollectiveSite]
        self.collectives: dict = {}
        #: fkey -> list[(line, call name)] global-placement calls
        self.placements: dict = {}
        #: fkey -> first pallas_call line in the function
        self.pallas_fns: dict = {}
        #: fkey -> list[DonatedCallable] callable by that function
        self.donations: dict = {}
        #: fkey -> {ctx_id: (Context, parent fkey | None, call line | None)}
        self.contexts: dict = {}
        #: path -> [MeshVal] mesh literals minted anywhere in the module
        self.minted_meshes: dict = {}
        #: (FunctionInfo, NamedSharding ast.Call) pairs, recorded during
        #: the ONE site scan so S002 never re-walks the package
        self.named_sharding_calls: list = []
        #: id(ctx) -> [(node, qual)]: the module-level walk runs once per
        #: module, not once per pass that needs it
        self._mod_nodes_cache: dict = {}
        #: fkeys of functions that run under jit (jit(f)/pjit(f) call
        #: sites resolved through the graph -- factory forms included --
        #: plus @jit-style decorators); found during the ONE site scan,
        #: never by rebuilding rules_jax's per-module _JitIndex
        self.jit_entries: set = set()
        self._build_factories()
        self._build_module_consts()
        self._build_envs()
        self._flow_params()
        self._scan_sites()
        self._propagate_contexts()

    # -- literal extraction ---------------------------------------------------
    def _axes_of_mesh_call(self, call: ast.Call) -> "tuple | None":
        """Axis names of a ``Mesh(devices, axis_names)`` literal."""
        arg = None
        kw = keyword(call, "axis_names")
        if kw is not None:
            arg = kw.value
        elif len(call.args) >= 2:
            arg = call.args[1]
        if arg is None:
            return None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return (arg.value,)
        if isinstance(arg, (ast.Tuple, ast.List)):
            names = []
            for el in arg.elts:
                if not (isinstance(el, ast.Constant) and isinstance(el.value, str)):
                    return None
                names.append(el.value)
            return tuple(names)
        return None

    def _axes_of_spec_call(self, call: ast.Call) -> tuple:
        """Axis-name strings a P/PartitionSpec literal binds (``None``
        placeholders and nested tuples like ``P(("data","model"))``
        flatten; non-constant entries are skipped, not guessed)."""
        names = []
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            for node in ast.walk(arg):
                if isinstance(node, ast.Constant) and isinstance(node.value, str):
                    names.append(node.value)
        return tuple(names)

    def _literal_val(self, owner_path, owner_qual, expr) -> "set | None":
        """Mesh/spec vals a LITERAL expression denotes, else None."""
        if not isinstance(expr, ast.Call):
            return None
        last = _last(call_name(expr))
        if last in _MESH_CTORS:
            axes = self._axes_of_mesh_call(expr)
            if axes is not None:
                return {MeshVal(axes, owner_path, owner_qual, expr.lineno)}
            return set()
        if last in _SPEC_CTORS:
            return {SpecVal(
                self._axes_of_spec_call(expr), "PartitionSpec",
                owner_path, owner_qual, expr.lineno,
            )}
        if last in _NAMED_CTORS and expr.args:
            spec_axes: tuple = ()
            if len(expr.args) >= 2:
                inner = self._literal_val(owner_path, owner_qual, expr.args[1])
                for v in inner or ():
                    if isinstance(v, SpecVal):
                        spec_axes = v.axes
            return {SpecVal(
                spec_axes, "NamedSharding", owner_path, owner_qual,
                expr.lineno,
            )}
        return None

    # -- factories ------------------------------------------------------------
    def _build_factories(self) -> None:
        """Functions whose every ``return`` is a mesh literal (or a call
        to an already-summarized factory) summarize to that axis tuple --
        ``parallel/mesh.py``'s ``local_mesh`` is the canonical entry."""
        for _ in range(3):
            grew = False
            for fi in self.graph.functions.values():
                if fi.key in self.factory_axes:
                    continue
                axes = self._factory_summary(fi)
                if axes is not None:
                    self.factory_axes[fi.key] = axes
                    grew = True
            if not grew:
                break

    def _factory_summary(self, fi) -> "tuple | None":
        axes: "tuple | None" = None
        saw_return = False
        for node in self.graph.body_nodes(fi.node):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            saw_return = True
            got = self._return_mesh_axes(fi, node.value)
            if got is None:
                return None
            if axes is None:
                axes = got
            elif axes != got:
                return None
        return axes if saw_return else None

    def _return_mesh_axes(self, fi, expr) -> "tuple | None":
        if isinstance(expr, ast.Call):
            last = _last(call_name(expr))
            if last in _MESH_CTORS:
                return self._axes_of_mesh_call(expr)
            for target in self.graph.resolve_call(fi, expr):
                if target.key in self.factory_axes:
                    return self.factory_axes[target.key]
        return None

    # -- environments ---------------------------------------------------------
    def _module_level_nodes(self, ctx):
        """Module statements outside any def/lambda (class bodies kept:
        class-level spec constants are real mint sites). Returns
        ``(node, qual)`` pairs with the enclosing-class qualname computed
        inline -- never ``ctx.symbol_for``, whose lazy full-module symbol
        map is exactly the cost the pre-commit budget cannot pay."""
        cached = self._mod_nodes_cache.get(id(ctx))
        if cached is None:
            cached = list(self._walk_module_level(ctx))
            self._mod_nodes_cache[id(ctx)] = cached
        return cached

    def _walk_module_level(self, ctx):
        stack = [(n, "<module>") for n in ast.iter_child_nodes(ctx.tree)]
        while stack:
            node, qual = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            if isinstance(node, ast.ClassDef):
                inner = node.name if qual == "<module>" else f"{qual}.{node.name}"
                yield node, qual
                stack.extend(
                    (n, inner) for n in ast.iter_child_nodes(node)
                )
                continue
            yield node, qual
            stack.extend((n, qual) for n in ast.iter_child_nodes(node))

    def _build_module_consts(self) -> None:
        for ctx in self.index.contexts:
            consts: dict = {}
            for node, _qual in self._module_level_nodes(ctx):
                if not isinstance(node, ast.Assign):
                    continue
                vals = self._literal_val(ctx.path, "<module>", node.value)
                if not vals:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        consts.setdefault(t.id, set()).update(vals)
            if consts:
                self.module_consts[ctx.path] = consts

    def _build_envs(self) -> None:
        # ONE Assign pass per function builds both the value env and the
        # donation map (the pre-commit budget pays for every extra body
        # walk)
        for fi in self.graph.functions.values():
            env: dict = {}
            for node in self.graph.body_nodes(fi.node):
                if not isinstance(node, ast.Assign):
                    continue
                if isinstance(node.value, ast.Call):
                    self._collect_donation(fi, node)
                vals = self._value_of(fi, node.value, env)
                if not vals:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        env.setdefault(t.id, set()).update(vals)
                    else:
                        d = dotted(t)
                        if d and d.startswith("self.") and d.count(".") == 1 \
                                and fi.cls is not None:
                            self.attr_vals.setdefault(
                                (fi.path, fi.cls, d[5:]), set()
                            ).update(vals)
            if env:
                self.fn_env[fi.key] = env

    def _value_of(self, fi, expr, env=None) -> set:
        """Mesh/spec vals an expression may denote: literals, local env,
        module constants, interprocedural param bindings, ``self.attr``,
        and calls to summarized mesh factories."""
        lit = self._literal_val(fi.path, fi.qual, expr)
        if lit is not None:
            return lit
        if isinstance(expr, ast.Call):
            out: set = set()
            for target in self.graph.resolve_call(fi, expr):
                axes = self.factory_axes.get(target.key)
                if axes is not None:
                    out.add(MeshVal(axes, fi.path, fi.qual, expr.lineno))
            return out
        if isinstance(expr, ast.Name):
            if env is None:
                env = self.fn_env.get(fi.key, {})
            hit = env.get(expr.id)
            if hit:
                return set(hit)
            bound = self.param_vals.get((fi.key, expr.id))
            if bound:
                return set(bound)
            if expr.id in fi.params():
                # a parameter SHADOWS any same-named module constant --
                # its value is whatever the caller passes, and with no
                # interprocedural binding that is honestly unknown
                return set()
            consts = self.module_consts.get(fi.path, {})
            hit = consts.get(expr.id)
            if hit:
                return set(hit)
            return set()
        d = dotted(expr)
        if d and d.startswith("self.") and d.count(".") == 1 and fi.cls:
            return set(self.attr_vals.get((fi.path, fi.cls, d[5:]), ()))
        return set()

    # -- interprocedural value flow ------------------------------------------
    def _flow_params(self) -> None:
        """Push known mesh/spec values through resolved call arguments
        into callee parameters, recording the hand-off hop -- iterated to
        a fixpoint so a mesh minted two frames up still lands.

        Gated for the pre-commit budget: only functions that can PRODUCE
        a value (a non-empty local env, a module with mesh/spec
        constants, class attrs holding values, or values already bound to
        their params) evaluate Name arguments; everything else evaluates
        only Call arguments (an inline factory/ctor literal can appear
        anywhere). ~95% of the package never touches the domain and
        skips the per-argument work entirely."""
        by_mod: dict = {}
        by_cls: dict = {}
        for fi in self.graph.functions.values():
            by_mod.setdefault(fi.path, []).append(fi.key)
            if fi.cls is not None:
                by_cls.setdefault((fi.path, fi.cls), []).append(fi.key)
        interesting: set = set(self.fn_env)
        for path in self.module_consts:
            interesting.update(by_mod.get(path, ()))
        for (path, cls, _attr) in self.attr_vals:
            interesting.update(by_cls.get((path, cls), ()))
        for _ in range(_MAX_FIXPOINT):
            changed = False
            for fi in self.graph.functions.values():
                rich = fi.key in interesting
                for cs in self.graph.callees(fi.key):
                    if not cs.targets or not (
                        cs.call.args or cs.call.keywords
                    ):
                        continue
                    changed |= self._flow_call(fi, cs, rich, interesting)
            if not changed:
                break

    def _flow_call(self, fi, cs, rich: bool, interesting: set) -> bool:
        changed = False
        hop = f"{fi.path}:{fi.qual}:{cs.line}"
        for target in cs.targets:
            params = target.params()
            offset = 1 if params[:1] == ["self"] else 0
            pairs = []
            for i, arg in enumerate(cs.call.args):
                if i + offset < len(params):
                    pairs.append((params[i + offset], arg))
            for kw in cs.call.keywords:
                if kw.arg is not None and kw.arg in params:
                    pairs.append((kw.arg, kw.value))
            for pname, arg in pairs:
                if not rich and not isinstance(arg, ast.Call):
                    continue
                vals = self._value_of(fi, arg)
                if not vals:
                    continue
                cur = self.param_vals.setdefault((target.key, pname), set())
                for v in vals:
                    if len(v.trail) >= _MAX_TRAIL:
                        continue
                    forwarded = self._with_hop(v, hop)
                    if forwarded not in cur:
                        cur.add(forwarded)
                        changed = True
                        interesting.add(target.key)
        return changed

    @staticmethod
    def _with_hop(val, hop: str):
        if hop in val.trail or hop == val.site:
            return val
        if isinstance(val, MeshVal):
            return MeshVal(val.axes, val.path, val.qual, val.line,
                           val.trail + (hop,))
        return SpecVal(val.axes, val.kind, val.path, val.qual, val.line,
                       val.trail + (hop,))

    # -- site scan ------------------------------------------------------------
    def _scan_sites(self) -> None:
        for fi in self.graph.functions.values():
            for node in self.graph.body_nodes(fi.node):
                if isinstance(node, ast.Call):
                    self._classify_call(fi, fi.path, fi.qual, node)
            if self._has_jit_decorator(fi.node):
                self.jit_entries.add(fi.key)
        for ctx in self.index.contexts:
            for node, qual in self._module_level_nodes(ctx):
                if isinstance(node, ast.Call):
                    self._classify_call(None, ctx.path, qual, node)
        self.sites.sort(key=lambda s: (s.path, s.line, s.kind))
        self.shardmap_sites.sort(key=lambda s: (s.fi.path, s.line))

    def _classify_call(self, fi, path: str, qual: str, call: ast.Call) -> None:
        name = call_name(call)
        last = _last(name)
        if last in _MESH_CTORS:
            axes = self._axes_of_mesh_call(call)
            if axes is not None:
                self.minted_meshes.setdefault(path, []).append(
                    MeshVal(axes, path, qual, call.lineno)
                )
            self.sites.append(Site(
                "mesh", path, qual, call.lineno,
                f"axes={list(axes)}" if axes is not None else "axes=<dynamic>",
            ))
        elif last in _SPEC_CTORS:
            axes = self._axes_of_spec_call(call)
            self.sites.append(Site(
                "partition_spec", path, qual, call.lineno,
                f"binds={list(axes)}" if axes else "replicated",
            ))
        elif last in _NAMED_CTORS:
            axes: tuple = ()
            if len(call.args) >= 2:
                inner = self._literal_val(path, qual, call.args[1])
                for v in inner or ():
                    if isinstance(v, SpecVal):
                        axes = v.axes
            if fi is not None and call.args:
                self.named_sharding_calls.append((fi, call))
            self.sites.append(Site(
                "named_sharding", path, qual, call.lineno,
                f"spec binds={list(axes)}" if axes else "spec=<resolved at use>",
            ))
        elif self._is_shard_map_call(fi, call, last):
            if fi is not None:
                self._record_shard_map(fi, call)
        elif last in _JIT_LAST:
            if call.args:
                if fi is not None:
                    for target in self.graph.resolve_callable(
                        fi, call.args[0]
                    ):
                        self.jit_entries.add(target.key)
                elif isinstance(call.args[0], ast.Name):
                    mod = self.graph.by_path.get(path)
                    hit = mod.top.get(call.args[0].id) if mod else None
                    if hit is not None:
                        self.jit_entries.add(hit.key)
            shard_kws = [
                kw.arg for kw in call.keywords
                if kw.arg in ("in_shardings", "out_shardings",
                              "donate_argnums", "donate_argnames")
            ]
            if shard_kws:
                self.sites.append(Site(
                    "sharded_jit", path, qual, call.lineno,
                    f"{name}({', '.join(sorted(shard_kws))})",
                ))
        elif last in _COLLECTIVES and fi is not None:
            axes = self._collective_axes(call, last)
            self.collectives.setdefault(fi.key, []).append(
                CollectiveSite(fi, call.lineno, last, axes)
            )
        elif last in _GLOBAL_PLACEMENT and fi is not None:
            self.placements.setdefault(fi.key, []).append((call.lineno, name))
        elif last == "pallas_call" and fi is not None:
            self.pallas_fns.setdefault(fi.key, call.lineno)

    @staticmethod
    def _has_jit_decorator(node: ast.AST) -> bool:
        """``@jax.jit`` / ``@jit`` / ``@functools.partial(jax.jit, ...)``
        decorations, checked on the def node alone (no module walk)."""
        for dec in getattr(node, "decorator_list", ()):
            d = dotted(dec)
            if d is not None and _last(d) in _JIT_LAST:
                return True
            if isinstance(dec, ast.Call):
                name = call_name(dec)
                if _last(name) in _JIT_LAST:
                    return True
                if _last(name) == "partial" and dec.args and _last(
                    dotted(dec.args[0]) or ""
                ) in _JIT_LAST:
                    return True
        return False

    def _is_shard_map_call(self, fi, call: ast.Call, last: str) -> bool:
        """A shard_map-former: the jax API name itself, a call with a
        ``mesh`` keyword, or a package wrapper whose resolved signature
        takes a ``mesh`` parameter (``seq_parallel_shard_map``). Plain
        helpers that merely END with ``_shard_map`` (this analyzer's
        own ``_record_shard_map``) do not count. The drift shim's
        internal forwarding (``utils/jax_compat.py``) is excluded too:
        seeding contexts there would union every caller's body against
        every caller's mesh."""
        if not last.endswith("shard_map") or not call.args:
            return False
        if fi is not None and fi.path.endswith("utils/jax_compat.py"):
            return False
        if last == "shard_map" or keyword(call, "mesh") is not None:
            return True
        if fi is not None:
            for target in self.graph.resolve_callable(fi, call.func):
                if "mesh" in target.params():
                    return True
        return False

    def _collective_axes(self, call: ast.Call, op: str) -> tuple:
        arg = None
        kw = keyword(call, "axis_name")
        if kw is not None:
            arg = kw.value
        else:
            idx = _COLLECTIVES[op]
            if idx < len(call.args):
                arg = call.args[idx]
        if arg is None:
            return ()
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return (arg.value,)
        if isinstance(arg, (ast.Tuple, ast.List)):
            names = []
            for el in arg.elts:
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    names.append(el.value)
                else:
                    return ()
            return tuple(names)
        return ()

    def _record_shard_map(self, fi, call: ast.Call) -> None:
        body_expr = call.args[0] if call.args else None
        bodies: list = []
        if body_expr is not None:
            bodies = self.graph.resolve_callable(fi, body_expr)
            if not bodies and isinstance(body_expr, ast.Name) \
                    and body_expr.id in set(fi.params()):
                bodies = sorted(
                    self.graph.param_bindings.get(
                        (fi.key, body_expr.id), ()
                    ),
                    key=lambda f: f.key,
                )
        mesh_expr = None
        kw = keyword(call, "mesh")
        if kw is not None:
            mesh_expr = kw.value
        elif len(call.args) >= 2:
            mesh_expr = call.args[1]
        mesh_vals = [
            v for v in (
                self._value_of(fi, mesh_expr) if mesh_expr is not None else ()
            )
            if isinstance(v, MeshVal)
        ]
        spec_axes: list = []
        for kwname in ("in_specs", "out_specs"):
            kw = keyword(call, kwname)
            if kw is not None:
                for node in ast.walk(kw.value):
                    if isinstance(node, ast.Call) and \
                            _last(call_name(node)) in _SPEC_CTORS:
                        spec_axes.extend(self._axes_of_spec_call(node))
        # a FORWARDING site -- body and mesh both bare parameters of the
        # enclosing wrapper (the seq_parallel_shard_map shape) -- must
        # not seed contexts: param bindings union EVERY caller's body
        # against EVERY caller's mesh, convicting correct code under a
        # mesh it never runs with. The caller-side sites (detected via
        # the wrapper's `mesh` parameter) carry the per-caller pairing.
        params = set(fi.params())
        forwarding = (
            isinstance(body_expr, ast.Name) and body_expr.id in params
            and isinstance(mesh_expr, ast.Name) and mesh_expr.id in params
        )
        if not forwarding:
            self.shardmap_sites.append(ShardMapSite(
                fi, call.lineno, call, bodies, mesh_vals,
                tuple(dict.fromkeys(spec_axes)),
            ))
        mesh_detail = sorted({str(list(v.axes)) for v in mesh_vals})
        self.sites.append(Site(
            "shard_map", fi.path, fi.qual, call.lineno,
            "forwarding wrapper (callers carry the body/mesh pairing)"
            if forwarding else
            "body={} mesh axes={} specs name {}".format(
                ",".join(sorted(b.qual for b in bodies)) or "<unresolved>",
                "/".join(mesh_detail) if mesh_detail else "<unresolved>",
                sorted(set(spec_axes)) if spec_axes else "[]",
            ),
        ))

    # -- donation map (S004) --------------------------------------------------
    def _collect_donation(self, fi, node: ast.Assign) -> None:
        """``x = jit(body, donate_argnums=...)`` / ``self.attr = jit(...)``
        assignments visible to this function: call-site positions that
        hand their buffer over. donate_argnames resolves against the
        jitted callee's parameters, via the graph."""
        don = self._donation_of(fi, node.value)
        if don is None:
            return
        positions = don
        for t in node.targets:
            d = dotted(t)
            if d is None:
                continue
            rec = DonatedCallable(d, node.value.lineno, positions)
            self.donations.setdefault(fi.key, []).append(rec)
            # class-attr donations are callable from sibling methods too
            if d.startswith("self.") and fi.cls is not None:
                key = (fi.path, fi.cls, "__donated__")
                self.attr_vals.setdefault(key, set()).add(
                    (d, node.value.lineno, positions)
                )

    def _donation_of(self, fi, call: ast.Call):
        """Donated positions of a jit call, else None."""
        if _last(call_name(call)) not in _JIT_LAST:
            return None
        params: list = []
        if call.args:
            for target in self.graph.resolve_callable(fi, call.args[0]):
                params = target.params()
                break
        positions: list = []
        for kwname in ("donate_argnums", "donate_argnames"):
            kw = keyword(call, kwname)
            if kw is None:
                continue
            value = kw.value
            if kwname == "donate_argnums":
                for c in ast.walk(value):
                    if isinstance(c, ast.Constant) and isinstance(c.value, int):
                        positions.append(c.value)
            else:
                for c in ast.walk(value):
                    if isinstance(c, ast.Constant) and isinstance(c.value, str):
                        if c.value in params:
                            positions.append(params.index(c.value))
        if not positions:
            return None
        return tuple(sorted(set(positions)))

    def donated_callables(self, fi) -> list:
        """DonatedCallables callable from ``fi``: its own assignments plus
        ``self.attr`` donations recorded anywhere on its class."""
        out = list(self.donations.get(fi.key, ()))
        if fi.cls is not None:
            for rec in self.attr_vals.get(
                (fi.path, fi.cls, "__donated__"), ()
            ):
                if isinstance(rec, tuple):
                    name, line, positions = rec
                    if not any(d.name == name for d in out):
                        out.append(DonatedCallable(name, line, positions))
        return out

    # -- context propagation --------------------------------------------------
    def _propagate_contexts(self) -> None:
        seeds: list = []   # (Context, body fkey)
        for site in self.shardmap_sites:
            if site.mesh_vals:
                # one context per resolved mesh candidate: a body fed two
                # different meshes is checked against each (per-path join)
                for mv in site.mesh_vals:
                    ctx = Context(
                        "shard_map",
                        f"{site.fi.path}:{site.fi.qual}:{site.line}",
                        mv.axes, mesh=mv,
                    )
                    for body in site.bodies:
                        seeds.append((ctx, body.key))
                continue
            ctx = Context(
                "shard_map",
                f"{site.fi.path}:{site.fi.qual}:{site.line}", None,
            )
            for body in site.bodies:
                seeds.append((ctx, body.key))
        for fkey in sorted(self.jit_entries):
            fi = self.graph.functions.get(fkey)
            if fi is None:
                continue
            seeds.append((
                Context("jit", f"{fi.path}:{fi.qual}:{fi.node.lineno}", None),
                fi.key,
            ))
        work: list = []
        for ctx, fkey in seeds:
            if fkey not in self.graph.functions:
                continue
            store = self.contexts.setdefault(fkey, {})
            ckey = (ctx.seed, ctx.axes)   # one seed, two meshes = two paths
            if ckey not in store:
                store[ckey] = (ctx, None, None)
                work.append((fkey, ctx))
        while work:
            fkey, ctx = work.pop()
            ckey = (ctx.seed, ctx.axes)
            for cs in self.graph.callees(fkey):
                for target in cs.targets:
                    store = self.contexts.setdefault(target.key, {})
                    if ckey in store:
                        continue
                    store[ckey] = (ctx, fkey, cs.line)
                    work.append((target.key, ctx))

    def contexts_of(self, fkey, kind: "str | None" = None) -> list:
        out = []
        for ctx, _parent, _line in self.contexts.get(fkey, {}).values():
            if kind is None or ctx.kind == kind:
                out.append(ctx)
        return out

    def witness_path(self, fkey, ctx: Context) -> list:
        """Call chain from the context's seed down to ``fkey``:
        ``["path:qual:line", ...]`` hops, seed site first."""
        ckey = (ctx.seed, ctx.axes)
        chain: list = []
        cur = fkey
        seen: set = set()
        while cur is not None and cur not in seen:
            seen.add(cur)
            rec = self.contexts.get(cur, {}).get(ckey)
            if rec is None:
                break
            _ctx, parent, line = rec
            fi = self.graph.functions.get(cur)
            if parent is None:
                # the seed's entry function itself (the shard_map body /
                # the jitted def), line-less like threadroles' entry hop
                chain.append(f"{fi.path}:{fi.qual}" if fi else str(cur))
                break
            chain.append(f"{fi.path}:{fi.qual}:{line}" if fi else str(cur))
            cur = parent
        chain.reverse()
        return [ctx.seed] + chain

    def env_meshes(self, fkey) -> list:
        """Every MeshVal visible to a function (locals, params, module
        constants, class attrs) -- the S003 multi-axis-mesh evidence."""
        out: list = []
        fi = self.graph.functions.get(fkey)
        if fi is None:
            return out
        for vals in self.fn_env.get(fkey, {}).values():
            out.extend(v for v in vals if isinstance(v, MeshVal))
        for (key, _param), vals in self.param_vals.items():
            if key == fkey:
                out.extend(v for v in vals if isinstance(v, MeshVal))
        for vals in self.module_consts.get(fi.path, {}).values():
            out.extend(v for v in vals if isinstance(v, MeshVal))
        if fi.cls is not None:
            for (path, cls, attr), vals in self.attr_vals.items():
                if path == fi.path and cls == fi.cls and attr != "__donated__":
                    out.extend(v for v in vals if isinstance(v, MeshVal))
        return out

    def module_meshes(self, path: str) -> list:
        """Every statically-known MeshVal a module mints or binds: mesh
        literals anywhere in the file plus factory-derived values in any
        of its function environments (the coarse S003 evidence -- a
        module that builds a 2x2 mesh somewhere is doing multi-axis
        placement)."""
        out = list(self.minted_meshes.get(path, ()))
        for (p, _qual), env in self.fn_env.items():
            if p != path:
                continue
            for vals in env.values():
                out.extend(v for v in vals if isinstance(v, MeshVal))
        for vals in self.module_consts.get(path, {}).values():
            out.extend(v for v in vals if isinstance(v, MeshVal))
        return out

    def report_sites(self) -> list:
        """The ``--mesh-report`` inventory as uniform site dicts for the
        shared report writer (``engine.render_site_report_*``): every
        mesh / PartitionSpec / NamedSharding / shard_map / sharded-jit
        construction site -- the worklist for extracting the shared MPMD
        executor layer."""
        return [
            {
                "kind": s.kind, "path": s.path, "qual": s.qual,
                "line": s.line, "detail": s.detail,
            }
            for s in self.sites
        ]
