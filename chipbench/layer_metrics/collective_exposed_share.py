"""Share of the traced window a device spends in collectives while no
compute runs on it. On the device's serial ops line a collective that is
on the line keeps compute off it, so the collectives' self time there is
their exposed part (the hidden part of an async collective lies between
its -start and -done and is not on the line)."""
LAYER = "sharding"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    red = (run.get("trace") or {}).get("reduced")
    if run["kind"] != "train" or run["chips"] < 2 or not red:
        return None
    return 100.0 * red["collective_exposed_s"] / red["window_s"]
