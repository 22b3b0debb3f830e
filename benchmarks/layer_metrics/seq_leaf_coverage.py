"""Share of the window's device-busy time that is named to a class of
operation: under a leaf, in a stage that is one class already (``mlp``, ``exit``,
``route``, ``experts``; ``layers`` with no stage below it, the scan's slicing
and stacking) or in ``seq.embed`` / ``seq.optimizer``; the ragged dots placed.
What is left is the self time of ``attention`` and ``moe``, the loops' own time
and the operations no control flow places."""

from benchmarks import scopes_leaf


def read(run):
    found = scopes_leaf.of_run(run)
    if found is None or not found["busy_s"]:
        return None
    return 100.0 * scopes_leaf.seconds(found, scopes_leaf.named) / found["busy_s"]
