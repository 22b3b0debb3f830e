"""The busiest held expert's assignments over the held experts' mean (any
layer against the mean of all), from what the window's steps returned."""


def read(run):
    counts = run.get("step_counts")
    if not counts or not counts.get("moe_held_load_mean"):
        return None
    return counts["moe_held_load_max"] / counts["moe_held_load_mean"]
