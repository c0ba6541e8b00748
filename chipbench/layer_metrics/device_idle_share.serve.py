"""Share of the traced window in which no operation ran on the device.
Below the knee the slots always hold work, so a gap is host time inside
or between ticks (the breakdown names the span that covers it)."""
LAYER = "device"
UNIT = "%"
MOVES = "tpot_p95_ms"


def compute(run):
    red = (run.get("trace") or {}).get("reduced")
    if run["kind"] != "serve" or not red:
        return None
    return 100.0 * red["idle_share"]
