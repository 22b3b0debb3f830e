"""Device milliseconds per step of the rotary positions and of the layout
around the attention programs together (the leaves ``rope``, and ``kernel``,
``index``, ``select`` less their device programs): in the sparse backbone's
step the compiler fuses the rotation of ``q`` and ``k`` into the cast that the
attention program reads, forward, and the casts' transposes into the
rotation's, backward, so the trace cannot tell the two apart."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(
        run, lambda p: (p.stage == "attention" and not p.program
                        and p.leaf in ("rope", "kernel", "index", "select")))
