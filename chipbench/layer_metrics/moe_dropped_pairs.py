"""(token, expert) pairs routed to an expert held here that found no row
in the layer's row buffer, summed over every step of the run and every
layer: the `dropped_pairs` buffers the compiled step adds to. The layer
is dropless while this is 0, and `correct` holds it to 0."""
LAYER = "expert layer"
UNIT = "count"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    counters = run.get("counters")
    if not counters or "dropped_pairs" not in counters:
        return None
    return counters["dropped_pairs"], "all steps, all layers"
