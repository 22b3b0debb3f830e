"""S-series rules: mesh / PartitionSpec / collective sharding semantics.

Built on ``analysis/meshflow.py``: the abstract sharding-facts domain
(mesh construction sites, spec literals, shard_map bindings, collectives,
donation maps) interpreted over PR 13's package call graph. Each finding
carries the mesh/spec CONSTRUCTION sites involved (``Finding.related``,
rendered as SARIF relatedLocations) plus a witness call path from the
binding site to the violation (``Finding.witness``, rendered as SARIF
codeFlows) -- a sharding bug report without the mesh it happened on is
not actionable.

Every rule class docstring IS its incident-catalog entry: ``pio check
--explain RULE`` prints it, and the S table in
``docs/static_analysis.md`` is generated from it.
"""

from __future__ import annotations

import ast
from typing import Iterator

from predictionio_tpu.analysis.astutil import dotted
from predictionio_tpu.analysis.engine import Finding
from predictionio_tpu.analysis.meshflow import MeshVal, SpecVal
from predictionio_tpu.analysis.packageindex import PackageIndex, PackageRule


def _related_of(*vals) -> tuple:
    """(path, line, label) mint-site triples for the report."""
    out = []
    for v in vals:
        if v is None:
            continue
        if isinstance(v, MeshVal):
            out.append((v.path, v.line, f"mesh constructed here (axes={list(v.axes)})"))
        elif isinstance(v, SpecVal):
            out.append((v.path, v.line,
                        f"{v.kind} constructed here (binds={list(v.axes)})"))
    return tuple(out)


def _trail_hops(val) -> list:
    return list(val.trail)


class RuleS001(PackageRule):
    """A collective (``psum``/``psum_scatter``/``all_gather``/
    ``axis_index``/...) over a string-literal axis name that no
    enclosing ``shard_map``/mesh binds on the witness path: either the
    function runs as (or below) a shard_map body whose resolved mesh
    lacks the axis, or it is reached from a jitted scope with no
    shard_map binding any axis at all. Unknown meshes and variable axis
    names stay silent -- the rule convicts only paths where the binding
    environment is statically known.

    Incident: the queued MPMD device-slice refactor (arxiv 2412.14374)
    ends the era of the global ``("data", "model")`` mesh singleton --
    per-engine slices mint their own meshes, and a
    ``psum_scatter(..., "model")`` helper that silently assumed the
    full mesh becomes an unbound-axis-name crash (or, under pmap-era
    fallbacks, a silent wrong-denominator mean) the first time a
    data-only slice calls it. ``parallel/als.py``'s
    ``_sharded_block_body`` is exactly such a helper three frames below
    its mesh construction."""

    rule_id = "S001"
    severity = "error"

    def check_package(self, index: PackageIndex) -> Iterator[Finding]:
        flow = index.meshflow()
        for fkey, sites in sorted(flow.collectives.items()):
            fi = flow.graph.functions.get(fkey)
            if fi is None:
                continue
            smap_ctxs = flow.contexts_of(fkey, "shard_map")
            jit_ctxs = flow.contexts_of(fkey, "jit")
            for site in sites:
                if not site.axes:
                    continue   # variable axis name: honestly unknown
                yield from self._check_site(flow, fi, site, smap_ctxs, jit_ctxs)

    def _check_site(self, flow, fi, site, smap_ctxs, jit_ctxs):
        for ctx in smap_ctxs:
            if ctx.axes is None:
                continue   # unknown mesh binds everything: err quiet
            missing = [a for a in site.axes if a not in ctx.axes]
            if not missing:
                continue
            hops = tuple(
                flow.witness_path(fi.key, ctx)
                + [f"{fi.path}:{fi.qual}:{site.line}"]
            )
            yield Finding(
                self.rule_id, self.severity, fi.path, site.line, fi.qual,
                f"collective `{site.op}` over axis "
                f"{'/'.join(repr(m) for m in missing)} which the enclosing "
                f"shard_map's mesh (axes={list(ctx.axes)}) does not bind "
                f"(witness path: {' -> '.join(hops)})",
                "run the collective over an axis of the mesh the shard_map "
                "actually binds, or thread the right mesh to this call",
                witness=hops,
                related=_related_of(ctx.mesh),
            )
        # per-path, never per-function: a shard_map route elsewhere must
        # not amnesty a separate unwrapped jit path to the same
        # collective (context propagation does not cross shard_map
        # boundaries, so a jit context here IS an unwrapped call chain)
        if jit_ctxs:
            ctx = jit_ctxs[0]
            hops = tuple(
                flow.witness_path(fi.key, ctx)
                + [f"{fi.path}:{fi.qual}:{site.line}"]
            )
            yield Finding(
                self.rule_id, self.severity, fi.path, site.line, fi.qual,
                f"collective `{site.op}` over axis "
                f"{'/'.join(repr(a) for a in site.axes)} with no enclosing "
                f"shard_map binding it on the witness path from the jitted "
                f"scope (witness path: {' -> '.join(hops)})",
                "wrap the collective-running body in shard_map over a mesh "
                "that binds the axis (the parallel/als.py routing)",
                witness=hops,
            )


class RuleS002(PackageRule):
    """A PartitionSpec placed on a mesh whose axis names do not include
    the spec's: ``NamedSharding(mesh, P("model"))`` -- or shard_map
    in/out specs naming an axis -- where the mesh that actually arrives
    (resolved interprocedurally through the call graph, so a spec minted
    in one module and consumed frames down in another is joined against
    the real mesh) lacks that axis name. Both construction sites land in
    the finding.

    Incident: the exact hazard of the MPMD slice refactor, where meshes
    stop being global singletons -- today every mesh is
    ``local_mesh()``'s ``("data", "model")`` and a stray ``P("model")``
    can't miss; the moment per-engine slices mint single-axis meshes, a
    spec routed onto the wrong mesh raises at best
    (``KeyError: 'model'``) and at worst silently replicates an array
    the caller believed was sharded -- the memory-blowup twin of the
    0.4.37 concat->reshard incident (J005)."""

    rule_id = "S002"
    severity = "error"

    def check_package(self, index: PackageIndex) -> Iterator[Finding]:
        flow = index.meshflow()
        seen: set = set()
        # NamedSharding call sites come from meshflow's ONE site scan --
        # re-walking every body here would double the package traversal
        # on the pre-commit path
        for fi, node in flow.named_sharding_calls:
            yield from self._check_named(flow, fi, node, seen)
        for site in flow.shardmap_sites:
            yield from self._check_shard_map(flow, site, seen)

    def _check_named(self, flow, fi, call, seen):
        mesh_vals = [
            v for v in flow._value_of(fi, call.args[0])
            if isinstance(v, MeshVal)
        ]
        spec_vals = []
        if len(call.args) >= 2:
            spec_vals = [
                v for v in flow._value_of(fi, call.args[1])
                if isinstance(v, SpecVal)
            ]
        for mv in mesh_vals:
            for sv in spec_vals:
                missing = [a for a in sv.axes if a not in mv.axes]
                if not missing:
                    continue
                key = (fi.path, call.lineno, mv.site, sv.site)
                if key in seen:
                    continue
                seen.add(key)
                hops = tuple(
                    [sv.site] + _trail_hops(sv)
                    + [f"{fi.path}:{fi.qual}:{call.lineno}"]
                )
                yield Finding(
                    self.rule_id, self.severity, fi.path, call.lineno,
                    fi.qual,
                    f"PartitionSpec binding {'/'.join(repr(m) for m in missing)} "
                    f"placed on a mesh whose axes are {list(mv.axes)} "
                    f"(spec minted at {sv.site}, mesh at {mv.site}; "
                    f"witness path: {' -> '.join(hops)})",
                    "build the spec from the mesh's own axis names, or route "
                    "the intended mesh to this placement",
                    witness=hops,
                    related=_related_of(mv, sv),
                )

    def _check_shard_map(self, flow, site, seen):
        if not site.mesh_vals or not site.spec_axes:
            return
        fi = site.fi
        for mv in site.mesh_vals:
            missing = [a for a in site.spec_axes if a not in mv.axes]
            if not missing:
                continue
            key = (fi.path, site.line, mv.site, tuple(missing))
            if key in seen:
                continue
            seen.add(key)
            hops = tuple(
                [mv.site] + _trail_hops(mv)
                + [f"{fi.path}:{fi.qual}:{site.line}"]
            )
            yield Finding(
                self.rule_id, self.severity, fi.path, site.line, fi.qual,
                f"shard_map specs name axis "
                f"{'/'.join(repr(m) for m in missing)} but the bound mesh's "
                f"axes are {list(mv.axes)} (mesh minted at {mv.site}; "
                f"witness path: {' -> '.join(hops)})",
                "make the in/out specs name only axes of the mesh handed to "
                "this shard_map",
                witness=hops,
                related=_related_of(mv),
            )


class RuleS003(PackageRule):
    """A ``pallas_call`` reachable inside a jitted scope under a
    multi-axis mesh with NO enclosing shard_map on the path: the kernel
    is opaque to GSPMD, so the partitioner replicates its operands and
    runs the whole kernel per device -- silently wrong results or an
    out-of-memory, never an error. Evidence of the multi-axis mesh (a
    resolved mesh construction with >= 2 axis names visible to the
    jitted entry, the kernel's function, or any frame on the witness
    path) is required; single-device jit of a kernel stays silent, and
    reaching the kernel through a shard_map body is the blessed route.

    Incident: the "pallas_call is opaque to GSPMD" class -- a fused ALS
    Gram kernel (removed in PR 28) gave wrong sums the moment it was
    jitted under the 2x2 mesh without shard_map routing. The package's
    remaining ``pallas_call``s (``ops/mips``, ``ops/flash_attention``,
    ``models/ncf/kernel``) are what the rule guards now."""

    rule_id = "S003"
    severity = "error"

    def check_package(self, index: PackageIndex) -> Iterator[Finding]:
        flow = index.meshflow()
        for fkey, line in sorted(flow.pallas_fns.items()):
            fi = flow.graph.functions.get(fkey)
            if fi is None:
                continue
            # per-context, not per-kernel: a kernel can be reached BOTH
            # through a shard_map route and directly from another jit --
            # a shard_map path elsewhere must not amnesty an unwrapped
            # jit path
            jit_ctxs = flow.contexts_of(fkey, "jit")
            for ctx in jit_ctxs:
                mesh = self._multi_axis_evidence(flow, fkey, ctx)
                if mesh is None:
                    continue
                hops = tuple(
                    flow.witness_path(fkey, ctx)
                    + [f"{fi.path}:{fi.qual}:{line}"]
                )
                yield Finding(
                    self.rule_id, self.severity, fi.path, line, fi.qual,
                    f"pallas_call reached from jitted scope {ctx.seed} "
                    f"with no enclosing shard_map while a multi-axis mesh "
                    f"(axes={list(mesh.axes)}, minted at {mesh.site}) is in "
                    f"scope: the kernel is opaque to GSPMD "
                    f"(witness path: {' -> '.join(hops)})",
                    "route the kernel through an explicit shard_map over the "
                    "mesh (the parallel/als.py _sharded_block_body shape)",
                    witness=hops,
                    related=_related_of(mesh),
                )
                break   # one finding per kernel site is enough

    def _multi_axis_evidence(self, flow, fkey, ctx):
        """A >=2-axis MeshVal visible on the seed->kernel path, or --
        the jit constructor usually lives OUTSIDE that chain -- minted
        anywhere in the jit seed's or the kernel's module."""
        keys = [fkey]
        for hop in flow.witness_path(fkey, ctx):
            parts = hop.rsplit(":", 2)
            if len(parts) == 3:
                keys.append((parts[0], parts[1]))
        for key in keys:
            for mv in flow.env_meshes(key):
                if len(mv.axes) >= 2:
                    return mv
        seed_path = ctx.seed.rsplit(":", 2)[0]
        for path in dict.fromkeys((seed_path, fkey[0])):
            for mv in flow.module_meshes(path):
                if len(mv.axes) >= 2:
                    return mv
        return None


class RuleS004(PackageRule):
    """Read-after-donate: a caller invokes a jitted program that donates
    an argument buffer (``donate_argnums``/``donate_argnames``), then
    reads the donated argument's name after the call returns (or loops
    back into the call without rebinding it) -- the buffer was handed to
    XLA and may already hold the output. Rebinding the name from the
    call's result (``params, opt = step(params, opt)``) is the intended
    shape and stays silent.

    Incident: while chasing a corrupted tp-sharded adam state (PR 4), the
    debugging tail followed a caller that logged ``opt_state`` AFTER the
    donated step -- a read of a buffer that no longer belonged to it,
    returning plausible garbage that masked the real corruption for
    days."""

    rule_id = "S004"
    severity = "error"

    def check_package(self, index: PackageIndex) -> Iterator[Finding]:
        flow = index.meshflow()
        for fi in sorted(
            flow.graph.functions.values(), key=lambda f: f.key
        ):
            donated = {
                d.name: d for d in flow.donated_callables(fi)
            }
            if not donated:
                continue
            yield from self._check_function(flow, fi, donated)

    def _check_function(self, flow, fi, donated):
        body = flow.graph.body_nodes(fi.node)
        # name -> sorted line lists, loads and stores separately
        loads: dict = {}
        stores: dict = {}
        for node in body:
            d = None
            if isinstance(node, (ast.Name, ast.Attribute)):
                d = dotted(node)
            if d is None:
                continue
            ctx = getattr(node, "ctx", None)
            if isinstance(ctx, ast.Store):
                stores.setdefault(d, []).append(node.lineno)
            elif isinstance(ctx, ast.Load):
                loads.setdefault(d, []).append(node.lineno)
        loops = [
            (n.lineno, getattr(n, "end_lineno", n.lineno), n)
            for n in body
            if isinstance(n, (ast.For, ast.While, ast.AsyncFor))
        ]
        reported: set = set()
        for node in body:
            if not isinstance(node, ast.Call):
                continue
            callee = dotted(node.func)
            don = donated.get(callee or "")
            if don is None:
                continue
            for pos in don.positions:
                if pos >= len(node.args):
                    continue
                name = dotted(node.args[pos])
                if name is None:
                    continue
                yield from self._check_arg(
                    fi, don, node, name, loads, stores, loops, reported
                )

    def _check_arg(self, fi, don, call, name, loads, stores, loops, reported):
        line = call.lineno
        # the call's own argument lines are not "reads after": a
        # black-wrapped multi-line donated call puts the donated name on
        # a continuation line past call.lineno
        call_end = getattr(call, "end_lineno", line) or line
        # first rebinding at/after the donating call resets the hazard
        rebind = min(
            (ln for ln in stores.get(name, ()) if ln >= line),
            default=None,
        )
        horizon = rebind if rebind is not None else float("inf")
        late_reads = [
            ln for ln in loads.get(name, ())
            if call_end < ln < horizon
        ]
        enclosing = [
            (lo, hi) for lo, hi, _n in loops if lo <= line <= hi
        ]
        loop_hazard = None
        if enclosing and not any(
            lo <= ln <= hi
            for ln in stores.get(name, ())
            for lo, hi in enclosing
        ):
            loop_hazard = min(lo for lo, _hi in enclosing)
        if not late_reads and loop_hazard is None:
            return
        key = (fi.path, line, name)
        if key in reported:
            return
        reported.add(key)
        if late_reads:
            what = (
                f"{name!r} is read at line {late_reads[0]} after being "
                f"donated to the jitted call at line {line}"
            )
        else:
            what = (
                f"{name!r} is donated at line {line} inside the loop at "
                f"line {loop_hazard} and never rebound in the loop body: "
                f"the next iteration re-reads a donated buffer"
            )
        hops = (
            f"{fi.path}:{fi.qual}:{don.jit_line}",
            f"{fi.path}:{fi.qual}:{line}",
            f"{fi.path}:{fi.qual}:{late_reads[0] if late_reads else line}",
        )
        yield Finding(
            self.rule_id, self.severity, fi.path, line, fi.qual,
            f"read-after-donate: {what} (donation declared at line "
            f"{don.jit_line}; witness path: {' -> '.join(hops)})",
            "rebind the name from the call's result (params, opt = "
            "step(params, opt)), or stop donating a buffer the caller "
            "still needs",
            witness=hops,
            related=((fi.path, don.jit_line,
                      "donating jit constructed here"),),
        )


class RuleS005(PackageRule):
    """``device_put`` / ``with_sharding_constraint`` / ``put_global``
    inside a shard_map body (or any function on a call path below one):
    the body runs PER SHARD on per-shard values, and a global placement
    directive there either fails to trace or quietly re-places one
    shard's slice as if it were the global array. Placement belongs to
    the caller, before/after the shard_map boundary.

    Incident: the J005 era's debugging detour -- while chasing the
    0.4.37 concat->reshard miscompile, a ``with_sharding_constraint``
    was briefly pushed INSIDE ``_sharded_block_body`` to "pin" the
    bucket output, which traced on one jax version and crashed with an
    unbound-mesh error on the other; the durable fix
    (``dynamic_update_slice`` assembly in the CALLER, constraints only
    outside the shard_map) is the committed shape in
    ``parallel/als.py``."""

    rule_id = "S005"
    severity = "error"

    def check_package(self, index: PackageIndex) -> Iterator[Finding]:
        flow = index.meshflow()
        for fkey, sites in sorted(flow.placements.items()):
            fi = flow.graph.functions.get(fkey)
            if fi is None:
                continue
            ctxs = flow.contexts_of(fkey, "shard_map")
            if not ctxs:
                continue
            ctx = ctxs[0]
            for line, name in sites:
                hops = tuple(
                    flow.witness_path(fkey, ctx)
                    + [f"{fi.path}:{fi.qual}:{line}"]
                )
                yield Finding(
                    self.rule_id, self.severity, fi.path, line, fi.qual,
                    f"`{name}` inside a shard_map body: per-shard code "
                    f"applying global placement (bound at {ctx.seed}; "
                    f"witness path: {' -> '.join(hops)})",
                    "move the placement to the caller, outside the "
                    "shard_map boundary; inside the body every value is "
                    "already the local shard",
                    witness=hops,
                    related=_related_of(ctx.mesh),
                )


RULES = (RuleS001, RuleS002, RuleS003, RuleS004, RuleS005)
