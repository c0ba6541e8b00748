"""The program's side of a `xing4_0` configuration: the model the system
builds for a configuration file, and the leaves of its state that
`weights.generator`'s rule (matrices normal, vectors ones) does not fit.
`drivers/pretrain.py` finds this file by the configuration's
`model_type`.
"""
from __future__ import annotations

import numpy as np

from chipbench import weights

BIAS_STD = 0.02          # of the router's selection bias (see `generator`)
# how `generator` starts every half-layer's hyper-connection (Phi is a matrix:
# `weights.generator`'s normal(0, 0.02)); a configuration states the same
# under `hc_seed`, where its `assumed` says why
HC_SEED = {"phi_std": 0.02, "scale": 0.25, "b_pre": -1.0, "b_post": 0.0,
           "b_res_diagonal": 1.0, "b_res_off_diagonal": -1.0}
ZEROS = (".expert_tokens", ".dropped_pairs", ".res_sum_err", "main_loss",
         "mtp_loss")
YARN = ("factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim")


def model_config(cfg_json):
    """The program's model configuration for one configuration file: the
    vocabulary is the slice held here, the experts the ones held here."""
    from paddle_tpu.models.xing4_0 import Xing40Config
    total = cfg_json.get("reduced_from", {}).get(
        "n_routed_experts", cfg_json["n_routed_experts"])
    same = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
            "intermediate_size", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
            "moe_intermediate_size", "num_experts_per_tok",
            "n_shared_experts", "norm_topk_prob", "rms_norm_eps",
            "num_nextn_predict_layers", "hc_mult", "hc_sinkhorn_iters",
            "hc_eps")
    if (cfg_json["n_group"], cfg_json["topk_group"],
            cfg_json["topk_method"]) != (1, 1, "noaux_tc"):
        raise ValueError("the router limits its choice to groups: not built")
    rs = cfg_json.get("rope_scaling")
    if rs is not None and rs.get("type") != "yarn":
        raise ValueError(f"rope_scaling of type {rs.get('type')!r}: not built")
    seed = cfg_json.get("hc_seed", HC_SEED)
    if seed != HC_SEED:
        raise ValueError(f"hc_seed {seed}: the generator seeds {HC_SEED}")
    return Xing40Config(
        vocab_size=cfg_json.get("vocab_rows", cfg_json["vocab_size"]),
        rope_theta=float(cfg_json["rope_theta"]),
        rope_scaling=None if rs is None else tuple(rs[k] for k in YARN),
        n_routed_experts=total,
        routed_scaling_factor=float(cfg_json["routed_scaling_factor"]),
        mtp_loss_weight=float(cfg_json["mtp_loss_weight"]),
        mhc_h_res_clamp_min=float(cfg_json["mhc_h_res_clamp_min"]),
        mhc_h_res_clamp_max=float(cfg_json["mhc_h_res_clamp_max"]),
        hc_init=(seed["scale"], seed["b_pre"], seed["b_post"],
                 seed["b_res_diagonal"], seed["b_res_off_diagonal"]),
        experts_held=cfg_json["n_routed_experts"],
        expert_offset=cfg_json.get("expert_offset", 0),
        moe_rows=cfg_json.get("moe_rows"),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            cfg_json["torch_dtype"]],
        **{k: cfg_json[k] for k in same}, **cfg_json.get("program", {}))


def skeleton(cfg):
    """(model with no weights in it, {name: ShapeDtypeStruct}): the
    constructor traced abstractly, as `weights.skeleton` does for LLaMA."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.xing4_0 import Xing40ForCausalLM
    box = {}

    def build():
        box["model"] = Xing40ForCausalLM(cfg)
        return 0

    jax.eval_shape(build)
    paddle.seed(0)      # the traced constructor left a tracer as the key
    model = box["model"]
    shapes = {k: jax.ShapeDtypeStruct(tuple(v.data.shape), v.data.dtype)
              for k, v in model.state_dict().items()}
    return model, shapes


def generator(shapes, shardings=None):
    """`state(seed)` as `weights.generator` makes it (Phi is a matrix:
    normal(0, 0.02)), but for the router's selection bias, normal(0,
    BIAS_STD); the hyper-connection scales and biases, the configuration's
    `hc_seed` (the three scales one value; b_pre, b_post; b_res one value
    on the diagonal and one off it); and the step's counters and its two
    kept losses, zero."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.pieces import HyperConnection
    base = weights.generator(shapes, shardings)
    a, *biases = (HC_SEED[k] for k in (
        "scale", "b_pre", "b_post", "b_res_diagonal", "b_res_off_diagonal"))
    special = sorted(k for k in shapes if k.endswith(
        (".e_score_correction_bias", "_hc.scale", "_hc.bias") + ZEROS))

    def gen(seed):
        key = jax.random.fold_in(jax.random.key(seed), 0x61A4)
        out = {}
        for i, name in enumerate(special):
            s = shapes[name]
            if name.endswith("e_score_correction_bias"):
                out[name] = BIAS_STD * jax.random.normal(
                    jax.random.fold_in(key, i), s.shape, s.dtype)
            elif name.endswith("_hc.scale"):
                out[name] = jnp.full(s.shape, a, s.dtype)
            elif name.endswith("_hc.bias"):
                n = int(round((1 + s.shape[0]) ** 0.5)) - 1    # 2n + n^2
                out[name] = jnp.asarray(
                    HyperConnection.bias_start(n, *biases), s.dtype)
            else:
                out[name] = jnp.zeros(s.shape, s.dtype)
        return out

    jitted = jax.jit(gen, out_shardings=(
        {k: shardings[k] for k in special} if shardings else None))
    return lambda seed: {**base(seed),
                         **jitted(np.uint32(int(seed) % (2 ** 32)))}


def counters(model):
    """What the compiled step counted and kept of its last step, as host
    numbers (call it outside every timed region: reading waits for the
    device): the expert layers' rows and dropped pairs, the module's layer
    last, the two losses the step's one loss is made of, and the largest
    |row sum - 1| and |column sum - 1| of H_res over the last step's tokens
    and half-layers."""
    c = model.moe_counters()
    return {"expert_tokens": c["expert_tokens"].tolist(),
            "dropped_pairs": int(c["dropped_pairs"].sum()),
            "main_loss": float(np.asarray(model.main_loss.data)),
            "mtp_loss": float(np.asarray(model.mtp_loss.data)),
            "hc_res_sum_err": model.hc_counters()["res_sum_err"]}
