"""Assignments to the experts this program holds over all assignments, from
what the window's steps returned: an eighth where the router is even."""


def read(run):
    counts = run.get("step_counts")
    if not counts or not counts.get("moe_assignments"):
        return None
    return 100.0 * counts["moe_held_assignments"] / counts["moe_assignments"]
