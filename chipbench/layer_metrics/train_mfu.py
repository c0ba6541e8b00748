"""Model FLOP/s utilisation of the whole window: operations the forward
and backward passes REQUIRE per token (costs.train_flops_per_token)
times tokens per second per chip, over the chip's bf16 peak."""
LAYER = "compiled step"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    if run["kind"] != "train" or not run.get("peaks"):
        return None
    achieved = run["flops_per_token"] * run["tokens_per_s_chip"]
    return 100.0 * achieved / run["peaks"]["bf16_flops_per_s"]
