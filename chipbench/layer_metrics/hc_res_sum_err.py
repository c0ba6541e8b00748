"""How far the residual map H_res is from doubly stochastic after
`hc_sinkhorn_iters` iterations: the largest |row sum - 1| over the last
step's tokens and half-layers, as the compiled step counted it (its
`res_sum_err` buffers, read beside `dropped_pairs`); the note gives the
largest |column sum - 1| (the columns are normalised last: about hc_eps).
The reference prints its own maps' value for every checked step ("reference:
hc_res_sum_err by step ..."). None for a program without the counter."""
LAYER = "residual path"
UNIT = "abs"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    counters = run.get("counters")
    if not counters or "hc_res_sum_err" not in counters:
        return None
    rows, cols = counters["hc_res_sum_err"]
    cfg = run.get("config") or {}
    return rows, (f"largest |column sum - 1| {cols:.3g}; last step, all "
                  f"half-layers, {cfg.get('hc_sinkhorn_iters')} iterations")
