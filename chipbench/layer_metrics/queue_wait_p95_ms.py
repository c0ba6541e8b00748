"""95th percentile of the request ledger's `queue_wait` bucket
(`observability/reqtrace.py`) over the requests the window finished."""
LAYER = "serving engine"
UNIT = "ms"
MOVES = "ttft_p95_ms"


def compute(run):
    ledgers = run.get("ledgers")
    if not ledgers:
        return None
    from chipbench.harness import percentile
    return 1e3 * percentile([b.get("queue_wait", 0.0) for b in ledgers], 95)
