"""``pio check``: JAX-aware static analysis + interprocedural
concurrency lint.

Rule families (catalog with incidents: ``docs/static_analysis.md``;
``pio check --explain RULE`` prints any entry):

- **J-series** (``rules_jax``): the jax version-drift and tracing
  invariants -- drift-shim policy (J001), control flow on tracers (J003), host sync inside jit (J004),
  the 0.4.37 concat+reshard GSPMD miscompile (J005), loop-invariant
  h2d transfers (J006).
- **C-series** (``rules_concurrency``): built on the phase-2 whole-
  package core -- call graph (``callgraph``), thread-role inference
  (``threadroles``), lockset dataflow (``locksets``), shared via
  ``packageindex``. Lock-order cycles over call paths (C001), blocking
  I/O under caller-held locks (C002), fork-after-threads (C004),
  blocking calls reachable from flusher callbacks / event loops (C005),
  and the Eraser-style lockset race detector (C006, which replaced
  C003's allowlisted per-module walk).
- **R-series** (``rules_resources``): exception-path resource-lifecycle
  analysis on the phase-3 flowgraph layer (``flowgraph``): per-function
  CFGs with explicit exception edges and a must-release obligation
  domain, credited interprocedurally through the call graph. Permits/
  locks/fds leaked on exception paths (R001), spans neither finished
  nor detached (R002), tmp+fsync+rename / checkpoint-ordering
  durability violations (R003), obligations that die with no owner
  (R004).
- **S-series** (``rules_sharding``): sharding semantics on the phase-4
  meshflow layer (``meshflow``): mesh/PartitionSpec/NamedSharding
  construction sites, shard_map bindings, and collectives tracked as an
  abstract domain over the call graph. Collectives over unbound axis
  names (S001), specs placed on meshes lacking their axes (S002),
  pallas_call opaque to GSPMD outside shard_map under a multi-axis mesh
  (S003), read-after-donate (S004), global placement inside shard_map
  bodies (S005). ``pio check --mesh-report`` renders the same layer as
  the mesh/shard_map/spec site inventory.
- **P-series** (``rules_protocol``): cross-process protocol ordering on
  the phase-5 protocolflow layer (``protocols``): a declared table of
  each protocol's commit/publication/advance points, classified per call
  site and credited transitively over the call graph, plus per-module
  ``__main__`` process roles stitched through ring/portfile/notify
  edges. Ack reachable before its covering commit (P001), cursor
  advance before the consumer obligation completes (P002), unguarded
  cross-process version reads (P003), shard/partition moduli bypassing
  ``utils/stablehash`` (P004), handshake renames without covering fsync
  and READY files consumed without CRC verify (P005).
  ``pio check --protocol-report`` renders the same layer as the
  commit/publish/advance site inventory.

``analysis/baseline.json`` suppresses accepted findings (with mandatory
justifications; P entries additionally name the runtime test covering
the accepted risk); the tier-1 gate in ``tests/test_analysis.py``
asserts zero unsuppressed findings over the package. ``analysis/lockwatch.py``
and ``analysis/leakwatch.py`` are the runtime companions: lockwatch
validates C001 against actual acquisition orders under pytest and
records held locksets for C006's evidence; leakwatch watches span
lifecycles and package semaphore balances so an R-series leak a test
provokes fails that test with the site named.
"""

from predictionio_tpu.analysis.engine import (  # noqa: F401
    Finding,
    all_rules,
    apply_baseline,
    changed_files,
    check_paths,
    explain,
    load_baseline,
    parse_files,
    parse_source,
    render_rule_table,
    run_cli,
    self_check,
    update_docs,
)
