"""Device milliseconds per step of forward work run again in the backward pass:
``rematted_computation`` (a layer, a chunk of an exit's head, a chunk of the
experts) and ``again`` (the experts' forward half of their backward rule). XLA's
ragged dots carry no scope and are placed by the ``conditional`` that holds
them: those run again count under ``seq_backward_ms``."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.phase == "recomputed")
