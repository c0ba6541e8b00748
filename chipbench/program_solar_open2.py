"""The program's side of a `solar_open2` configuration: the model the
system builds for a configuration file, and the leaves of its state that
`weights.generator`'s rule (matrices normal, vectors ones) does not fit.
`drivers/pretrain.py` finds this file by the configuration's
`model_type`.
"""
from __future__ import annotations

import math

import numpy as np

from chipbench import weights


def model_config(cfg_json):
    """The program's model configuration for one configuration file: the
    vocabulary is the slice held here, the experts the ones held here."""
    from paddle_tpu.models.solar_open2 import SolarOpen2Config
    lin = cfg_json["linear_attn_config"]
    total = cfg_json.get("reduced_from", {}).get(
        "n_routed_experts", cfg_json["n_routed_experts"])
    return SolarOpen2Config(
        vocab_size=cfg_json.get("vocab_rows", cfg_json["vocab_size"]),
        hidden_size=cfg_json["hidden_size"],
        num_hidden_layers=cfg_json["num_hidden_layers"],
        num_attention_heads=cfg_json["num_attention_heads"],
        num_key_value_heads=cfg_json["num_key_value_heads"],
        head_dim=cfg_json["head_dim"],
        gqa_layers=tuple(cfg_json["gqa_layers"]),
        linear_num_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        kda_low_rank=cfg_json.get("kda_low_rank", lin["head_dim"]),
        moe_intermediate_size=cfg_json["moe_intermediate_size"],
        n_routed_experts=total,
        num_experts_per_tok=cfg_json["num_experts_per_tok"],
        n_shared_experts=cfg_json["n_shared_experts"],
        norm_topk_prob=cfg_json["norm_topk_prob"],
        routed_scaling_factor=float(cfg_json["routed_scaling_factor"]),
        rms_norm_eps=cfg_json["rms_norm_eps"],
        experts_held=cfg_json["n_routed_experts"],
        expert_offset=cfg_json.get("expert_offset", 0),
        moe_rows=cfg_json.get("moe_rows"),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            cfg_json["torch_dtype"]],
        **cfg_json.get("program", {}))


def skeleton(cfg):
    """(model with no weights in it, {name: ShapeDtypeStruct}): the
    constructor traced abstractly, as `weights.skeleton` does for LLaMA."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.solar_open2 import SolarOpen2ForCausalLM
    box = {}

    def build():
        box["model"] = SolarOpen2ForCausalLM(cfg)
        return 0

    jax.eval_shape(build)
    paddle.seed(0)      # the traced constructor left a tracer as the key
    model = box["model"]
    shapes = {k: jax.ShapeDtypeStruct(tuple(v.data.shape), v.data.dtype)
              for k, v in model.state_dict().items()}
    return model, shapes


def generator(shapes, shardings=None):
    """`state(seed)` as `weights.generator` makes it, but for: the decay's
    rate A_log = log U(1, 16) a head and its step dt_bias =
    softplus^-1(dt), log dt ~ U(log 1e-3, log 1e-1), so that heads
    remember from a few tokens to a few thousand (ones would forget
    within a token and hide the recurrence); and the counters, zero."""
    import jax
    import jax.numpy as jnp
    base = weights.generator(shapes, shardings)
    special = sorted(k for k in shapes if k.endswith(
        (".A_log", ".dt_bias", ".expert_tokens", ".dropped_pairs")))

    def gen(seed):
        key = jax.random.fold_in(jax.random.key(seed), 0x5017)
        out = {}
        for i, name in enumerate(special):
            s, k = shapes[name], jax.random.fold_in(key, i)
            if name.endswith(".A_log"):
                out[name] = jnp.log(jax.random.uniform(
                    k, s.shape, s.dtype, 1.0, 16.0))
            elif name.endswith(".dt_bias"):
                dt = jnp.exp(jax.random.uniform(
                    k, s.shape, s.dtype, math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                out[name] = jnp.zeros(s.shape, s.dtype)
        return out

    jitted = jax.jit(gen, out_shardings=(
        {k: shardings[k] for k in special} if shardings else None))
    return lambda seed: {**base(seed),
                         **jitted(np.uint32(int(seed) % (2 ** 32)))}


def counters(model):
    """What the compiled step counted, as host numbers (call it outside
    every timed region: reading waits for the device)."""
    c = model.moe_counters()
    return {"expert_tokens": c["expert_tokens"].tolist(),
            "dropped_pairs": int(c["dropped_pairs"].sum())}
