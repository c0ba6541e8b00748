"""Median wall milliseconds of one `engine.step()` (harness timer)."""
LAYER = "serving engine"
UNIT = "ms"
MOVES = "tpot_p95_ms"


def compute(run):
    ticks = run.get("tick_s")
    if not ticks:
        return None
    from chipbench.harness import percentile
    return 1e3 * percentile(ticks, 50)
