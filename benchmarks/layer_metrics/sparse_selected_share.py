"""Selected (query, key) pairs over causal pairs, from what the window's steps
returned (``selected_pairs``, ``causal_pairs``: real queries, every layer)."""


def read(run):
    counts = run.get("step_counts")
    if not counts or not counts.get("causal_pairs"):
        return None
    return 100.0 * counts["selected_pairs"] / counts["causal_pairs"]
