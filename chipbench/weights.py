"""Weights and token batches, made by the benchmark from --seed.

The system under test gets its weights from here and so does the plain
reference: neither takes anything the other has made. The whole state is
one jitted call on the device, in the dtype it is trained or served in
(bf16 matrices, float32 norm weights), placed by `shardings` where the
cell spans chips. The seed is an argument of that call, not a constant of
it, so every seed runs the same cached program.
"""
from __future__ import annotations

import numpy as np

INIT_STD = 0.02        # `initializer_range` of both public configs


def model_config(cfg_json):
    """The program's model configuration for one configuration file."""
    from paddle_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab_size=cfg_json["vocab_size"],
        hidden_size=cfg_json["hidden_size"],
        intermediate_size=cfg_json["intermediate_size"],
        num_hidden_layers=cfg_json["num_hidden_layers"],
        num_attention_heads=cfg_json["num_attention_heads"],
        num_key_value_heads=cfg_json["num_key_value_heads"],
        max_position_embeddings=cfg_json["max_position_embeddings"],
        rms_norm_eps=cfg_json["rms_norm_eps"],
        rope_theta=cfg_json["rope_theta"],
        tie_word_embeddings=cfg_json["tie_word_embeddings"],
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            cfg_json["torch_dtype"]])


def skeleton(cfg):
    """The program's model object with no weights in it: its constructor
    traced abstractly (`jax.eval_shape`), so no initializer runs and no
    memory is taken: at 32 layers the constructor's own float32 draft
    would not fit a chip. Every parameter holds a tracer until `install`
    gives it an array. Returns (model, {name: ShapeDtypeStruct})."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    box = {}

    def build():
        box["model"] = LlamaForCausalLM(cfg)
        return 0

    jax.eval_shape(build)
    paddle.seed(0)      # the traced constructor left a tracer as the key
    model = box["model"]
    shapes = {k: jax.ShapeDtypeStruct(tuple(v.data.shape), v.data.dtype)
              for k, v in model.state_dict().items()}
    return model, shapes


def generator(shapes, shardings=None):
    """`state(seed)` -> {name: array} for `shapes`, one jitted call on
    the device(s); 1-D leaves (RMSNorm weights) are ones, matrices
    normal(0, INIT_STD) in their own dtype."""
    import jax
    import jax.numpy as jnp
    names = sorted(shapes)

    def gen(seed):
        key = jax.random.key(seed)
        out = {}
        for i, name in enumerate(names):
            s = shapes[name]
            if len(s.shape) == 1:
                out[name] = jnp.ones(s.shape, s.dtype)
            else:
                out[name] = (jax.random.normal(
                    jax.random.fold_in(key, i), s.shape, jnp.float32)
                    * INIT_STD).astype(s.dtype)
        return out

    jitted = jax.jit(gen, out_shardings=shardings)
    return lambda seed: jitted(np.uint32(int(seed) % (2 ** 32)))


def install(model, state):
    """Hand `state`'s arrays to the model's parameters."""
    for name, t in model.state_dict().items():
        t.data = state[name]


def token_batches(seed, vocab, n, batch, seq):
    """`n` batches [batch, seq] of seeded random token ids (numpy int32),
    all rows different."""
    rng = np.random.default_rng([int(seed), 0x7A11])
    return rng.integers(0, vocab, (n, batch, seq)).astype(np.int32)
