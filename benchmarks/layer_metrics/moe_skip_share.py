"""Real tokens that chose the skip (the choice past the experts, whose output
is zero) over the real tokens a router saw, from what the window's steps
returned: one in seventeen where the router is even."""


def read(run):
    counts = run.get("step_counts")
    if not counts or "moe_skip_assignments" not in counts:
        return None
    choices = counts["moe_skip_assignments"] + counts["moe_assignments"]
    return 100.0 * counts["moe_skip_assignments"] / choices if choices else None
