"""The program's side of a `dots3_note` configuration: the model the
system builds for a configuration file, and the leaves of its state that
`weights.generator`'s rule (matrices normal, vectors ones) does not fit.
`drivers/pretrain.py` finds this file by the configuration's
`model_type`.
"""
from __future__ import annotations

import numpy as np

from chipbench import weights

BIAS_STD = 0.02          # of the router's selection bias (see `generator`)


def model_config(cfg_json):
    """The program's model configuration for one configuration file: the
    vocabulary is the slice held here, the experts the ones held here, the
    layers the `num_hidden_layers` entries of `layer_types` from
    `layer_offset` on (the pipeline stage's own)."""
    from paddle_tpu.models.dots3_note import Dots3NoteConfig
    total = cfg_json.get("reduced_from", {}).get(
        "n_routed_experts", cfg_json["n_routed_experts"])
    L, first = cfg_json["num_hidden_layers"], cfg_json.get("layer_offset", 0)
    same = ("hidden_size", "first_k_dense_replace", "intermediate_size",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "q_lora_rank", "kv_lora_rank", "index_n_heads",
            "index_head_dim", "index_topk", "swa_num_attention_heads",
            "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
            "swa_q_lora_rank", "swa_kv_lora_rank", "sliding_window_size",
            "moe_intermediate_size", "num_experts_per_tok",
            "n_shared_experts", "norm_topk_prob", "rms_norm_eps")
    return Dots3NoteConfig(
        vocab_size=cfg_json.get("vocab_rows", cfg_json["vocab_size"]),
        num_hidden_layers=L,
        layer_types=tuple(cfg_json["layer_types"][first:first + L]),
        rope_theta=float(cfg_json["rope_theta"]),
        swa_rope_theta=float(cfg_json["swa_rope_theta"]),
        mla_rescale=bool(cfg_json["apply_mla_qkv_lora_rescale"]),
        indexer_loss_weight=float(cfg_json.get("indexer_loss_weight", 1.0)),
        n_routed_experts=total,
        routed_scaling_factor=float(cfg_json["routed_scaling_factor"]),
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
    from paddle_tpu.models.dots3_note import Dots3NoteForCausalLM
    box = {}

    def build():
        box["model"] = Dots3NoteForCausalLM(cfg)
        return 0

    jax.eval_shape(build)
    paddle.seed(0)      # the traced constructor left a tracer as the key
    model = box["model"]
    shapes = {k: jax.ShapeDtypeStruct(tuple(v.data.shape), v.data.dtype)
              for k, v in model.state_dict().items()}
    return model, shapes


def generator(shapes, shardings=None):
    """`state(seed)` as `weights.generator` makes it, but for: the router's
    selection bias, normal(0, BIAS_STD) (small beside the scores' spread
    and not zero, so that the experts selected are not the largest scores
    and the weights are still the scores' own); the bias of the indexer's
    LayerNorm, normal(0, BIAS_STD) as well (ones would shift every key
    alike); and the counters, zero."""
    import jax
    import jax.numpy as jnp
    base = weights.generator(shapes, shardings)
    special = sorted(k for k in shapes if k.endswith(
        (".e_score_correction_bias", ".k_norm_bias", ".expert_tokens",
         ".dropped_pairs", ".attended_pairs")))

    def gen(seed):
        key = jax.random.fold_in(jax.random.key(seed), 0xD073)
        out = {}
        for i, name in enumerate(special):
            s = shapes[name]
            if name.endswith("_bias"):
                out[name] = BIAS_STD * jax.random.normal(
                    jax.random.fold_in(key, i), s.shape, s.dtype)
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
            "dropped_pairs": int(c["dropped_pairs"].sum()),
            "attended_pairs": c["attended_pairs"].tolist()}
