"""How unevenly the router loads the experts held here: the most rows
any held expert of any layer was sent in the last step over the mean
over all of them, from the `expert_tokens` buffers the compiled step
writes. 1.0 is a perfectly even load; the row buffer and the grouped
products are sized by the sum, the slowest expert by the largest."""
LAYER = "expert layer"
UNIT = "ratio"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    counters = run.get("counters")
    if not counters or not counters.get("expert_tokens"):
        return None
    rows = [n for layer in counters["expert_tokens"] for n in layer]
    mean = sum(rows) / len(rows)
    if mean <= 0:
        return None
    return max(rows) / mean, (f"rows per held expert, last step, by layer: "
                              f"{counters['expert_tokens']}")
