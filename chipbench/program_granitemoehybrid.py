"""The program's side of a `granitemoehybrid` configuration: the model the
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
    """The program's model configuration for one configuration file."""
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
    n = cfg_json["num_hidden_layers"]
    return GraniteHybridConfig(
        vocab_size=cfg_json["vocab_size"],
        hidden_size=cfg_json["hidden_size"],
        intermediate_size=cfg_json["shared_intermediate_size"],
        num_hidden_layers=n,
        layer_types=tuple(cfg_json["layer_types"][:n]),
        num_attention_heads=cfg_json["num_attention_heads"],
        num_key_value_heads=cfg_json["num_key_value_heads"],
        mamba_n_heads=cfg_json["mamba_n_heads"],
        mamba_d_head=cfg_json["mamba_d_head"],
        mamba_d_state=cfg_json["mamba_d_state"],
        mamba_n_groups=cfg_json["mamba_n_groups"],
        mamba_d_conv=cfg_json["mamba_d_conv"],
        mamba_chunk_size=cfg_json["mamba_chunk_size"],
        embedding_multiplier=float(cfg_json["embedding_multiplier"]),
        attention_multiplier=float(cfg_json["attention_multiplier"]),
        residual_multiplier=float(cfg_json["residual_multiplier"]),
        logits_scaling=float(cfg_json["logits_scaling"]),
        rms_norm_eps=cfg_json["rms_norm_eps"],
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            cfg_json["torch_dtype"]],
        **cfg_json.get("program", {}))


def skeleton(cfg):
    """(model with no weights in it, {name: ShapeDtypeStruct}): the
    constructor traced abstractly, as `weights.skeleton` does for LLaMA."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.granite_hybrid import GraniteHybridForCausalLM
    box = {}

    def build():
        box["model"] = GraniteHybridForCausalLM(cfg)
        return 0

    jax.eval_shape(build)
    paddle.seed(0)      # the traced constructor left a tracer as the key
    model = box["model"]
    shapes = {k: jax.ShapeDtypeStruct(tuple(v.data.shape), v.data.dtype)
              for k, v in model.state_dict().items()}
    return model, shapes


def generator(shapes, shardings=None):
    """`state(seed)` as `weights.generator` makes it (matrices normal(0,
    0.02), vectors ones: the norms and D), but for what the Mamba-2
    family initialises otherwise: the decay's rate A_log = log U(1, 16) a
    head and its step dt_bias = softplus^-1(dt), log dt ~ U(log 1e-3,
    log 1e-1), so that heads remember from a few tokens to a few thousand
    (ones would forget within a token and hide the recurrence); the
    depthwise convolution U(-1/2, 1/2) (1 / sqrt(taps); normal(0, 0.02)
    taps would shrink x, B and C fifty times and leave the layer its D
    skip alone) and its bias normal(0, 0.02)."""
    import jax
    import jax.numpy as jnp
    base = weights.generator(shapes, shardings)
    special = sorted(k for k in shapes if k.endswith(
        (".A_log", ".dt_bias", ".conv_weight", ".conv_bias")))

    def gen(seed):
        key = jax.random.fold_in(jax.random.key(seed), 0x6A17)
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
            elif name.endswith(".conv_weight"):
                bound = 1.0 / math.sqrt(s.shape[0])
                out[name] = jax.random.uniform(
                    k, s.shape, jnp.float32, -bound, bound).astype(s.dtype)
            else:
                out[name] = (jax.random.normal(k, s.shape, jnp.float32)
                             * weights.INIT_STD).astype(s.dtype)
        return out

    jitted = jax.jit(gen, out_shardings=(
        {k: shardings[k] for k in special} if shardings else None))
    return lambda seed: {**base(seed),
                         **jitted(np.uint32(int(seed) % (2 ** 32)))}


def counters(model):
    """What `drivers/pretrain.py` asks every program for: the model has no
    expert layer, so no pair was dropped and no expert saw a row."""
    return {"expert_tokens": [], "dropped_pairs": 0}
