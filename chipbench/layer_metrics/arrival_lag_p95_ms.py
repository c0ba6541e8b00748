"""How late the load generator ran: due time to `add_request`, 95th
percentile. A starved generator must not read as a fast server."""
LAYER = "harness"
UNIT = "ms"
MOVES = "ttft_p95_ms"


def compute(run):
    lags = run.get("arrival_lag_s")
    if not lags:
        return None
    from chipbench.harness import percentile
    return 1e3 * percentile(lags, 95)
