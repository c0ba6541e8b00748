"""Share of the traced window in which no operation ran on the device
(1 - busy union / window, averaged over the chips used)."""
LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    red = (run.get("trace") or {}).get("reduced")
    if run["kind"] != "train" or not red:
        return None
    return 100.0 * red["idle_share"]
