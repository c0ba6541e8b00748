"""Device milliseconds a step spends on the n-stream residual path, per
device: self time of every operation whose op_name resolves to `hc/map`
(the norm's statistic, the product with Phi, sigmoids, Sinkhorn),
`hc/pre`, `hc/post`, `hc/expand` or `hc/reduce`, and of the two Mosaic
calls of kernels/hyper_connection.py by their names, in the trunk's layers
and the prediction module's, forward, backward and recomputed, read through
the run's own `components_<model_type>.json` where it has the group `hc`.
None for a program that names no such scope."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"
FIRST = "components_xing4_0.json"


def compute(run):
    from chipbench import scope_tables
    if "hc_mult" not in (run.get("config") or {}):
        return None
    return scope_tables.ms_per_step(
        run, scope_tables.table_of(run, "hc", FIRST), "hc")
