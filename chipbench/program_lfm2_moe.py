"""The program's side of an `lfm2_moe` configuration: the model the system
builds for a configuration file, and the leaves of its state that
`weights.generator`'s rule (matrices normal, vectors ones) does not fit.
`drivers/pretrain.py` finds this file by the configuration's
`model_type`.
"""
from __future__ import annotations

import math

import numpy as np

from chipbench import weights

BIAS_STD = 0.02          # of the router's selection bias (see `generator`)
ZEROS = (".expert_tokens", ".dropped_pairs")


def model_config(cfg_json):
    """The program's model configuration for one configuration file: the
    vocabulary is the slice held here, the experts the ones held here, the
    layers the first `num_hidden_layers` of `layer_types`."""
    from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig
    if cfg_json["conv_bias"] or not cfg_json["use_expert_bias"]:
        raise ValueError("a convolution with a bias, or a router without "
                         "its selection bias: not built")
    n = cfg_json["num_hidden_layers"]
    same = ("hidden_size", "intermediate_size", "num_dense_layers",
            "num_attention_heads", "num_key_value_heads", "conv_L_cache",
            "norm_eps", "moe_intermediate_size", "num_experts_per_tok",
            "norm_topk_prob")
    return Lfm2MoeConfig(
        vocab_size=cfg_json.get("vocab_rows", cfg_json["vocab_size"]),
        num_hidden_layers=n,
        layer_types=tuple(cfg_json["layer_types"][:n]),
        rope_theta=float(cfg_json["rope_theta"]),
        num_experts=cfg_json.get("reduced_from", {}).get(
            "num_experts", cfg_json["num_experts"]),
        routed_scaling_factor=float(cfg_json["routed_scaling_factor"]),
        experts_held=cfg_json["num_experts"],
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
    from paddle_tpu.models.lfm2_moe import Lfm2MoeForCausalLM
    box = {}

    def build():
        box["model"] = Lfm2MoeForCausalLM(cfg)
        return 0

    jax.eval_shape(build)
    paddle.seed(0)      # the traced constructor left a tracer as the key
    model = box["model"]
    shapes = {k: jax.ShapeDtypeStruct(tuple(v.data.shape), v.data.dtype)
              for k, v in model.state_dict().items()}
    return model, shapes


def generator(shapes, shardings=None):
    """`state(seed)` as `weights.generator` makes it (matrices normal(0,
    0.02), vectors ones: the norms, a head's q and k norms among them),
    but for the depthwise convolution, U(-1/sqrt(taps), 1/sqrt(taps))
    (normal(0, 0.02) taps would shrink B * X fifty times and leave the
    layer its residual alone), the router's selection bias, normal(0,
    BIAS_STD) (small beside the scores' spread and not zero, so that the
    experts selected are not the largest scores and the weights are still
    the scores' own) less its mean over each chip's experts (runs of as
    many as are held: the family's bias is moved until the experts' loads
    are even, so every chip of the host gets about its share of the pairs;
    an uncentred draw gave this chip 0.93-1.07 of its quarter by seed and
    the step's rate followed the seed), and the step's counters, zero."""
    import jax
    import jax.numpy as jnp
    base = weights.generator(shapes, shardings)
    special = sorted(k for k in shapes if k.endswith(
        (".conv_weight", ".e_score_correction_bias") + ZEROS))

    def gen(seed):
        key = jax.random.fold_in(jax.random.key(seed), 0x1F32)
        out = {}
        for i, name in enumerate(special):
            s, k = shapes[name], jax.random.fold_in(key, i)
            if name.endswith(".conv_weight"):
                bound = 1.0 / math.sqrt(s.shape[0])
                out[name] = jax.random.uniform(
                    k, s.shape, jnp.float32, -bound, bound).astype(s.dtype)
            elif name.endswith("_bias"):
                held = shapes[name.rsplit(".", 1)[0]
                              + ".experts_gate_up"].shape[0]
                b = BIAS_STD * jax.random.normal(
                    k, s.shape, jnp.float32).reshape(-1, held)
                out[name] = (b - b.mean(axis=1, keepdims=True)).reshape(
                    s.shape).astype(s.dtype)
            else:
                out[name] = jnp.zeros(s.shape, s.dtype)
        return out

    jitted = jax.jit(gen, out_shardings=(
        {k: shardings[k] for k in special} if shardings else None))
    return lambda seed: {**base(seed),
                         **jitted(np.uint32(int(seed) % (2 ** 32)))}


def counters(model):
    """What the compiled step counted of its last step, as host numbers
    (call it outside every timed region: reading waits for the device):
    the expert layers' rows and dropped pairs."""
    c = model.moe_counters()
    return {"expert_tokens": c["expert_tokens"].tolist(),
            "dropped_pairs": int(c["dropped_pairs"].sum())}
