"""Fused blockwise cross-entropy kernel (kernels/cross_entropy.py) vs the
dense log-softmax reference — forward and backward, run through the Pallas
interpreter on the CPU mesh (ref: phi/kernels/gpu/cross_entropy_kernel.cu
fused softmax+CE)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.cross_entropy import fused_cross_entropy


def _dense_ce(logits, labels, ignore_index=-100):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0)
    picked = jnp.take_along_axis(logp, safe[:, None], axis=1)[:, 0]
    return jnp.where(valid, -picked, 0.0)


@pytest.mark.parametrize("n,v", [(512, 2048), (256, 3000), (64, 5000)])
def test_forward_matches_dense(n, v):
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((n, v)), jnp.float32) * 4.0
    labels = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    got = fused_cross_entropy(logits, labels)
    want = _dense_ce(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-5)


def test_ignore_index_rows_zero():
    rng = np.random.default_rng(1)
    n, v = 128, 2500
    logits = jnp.asarray(rng.standard_normal((n, v)), jnp.float32)
    labels = np.asarray(rng.integers(0, v, (n,)), np.int32)
    labels[::3] = -100
    labels = jnp.asarray(labels)
    got = fused_cross_entropy(logits, labels)
    assert np.all(np.asarray(got)[::3] == 0.0)
    want = _dense_ce(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_backward_matches_dense():
    rng = np.random.default_rng(2)
    n, v = 128, 2304
    logits = jnp.asarray(rng.standard_normal((n, v)), jnp.float32)
    labels = np.asarray(rng.integers(0, v, (n,)), np.int32)
    labels[5] = -100
    labels = jnp.asarray(labels)

    g_fused = jax.grad(
        lambda x: jnp.sum(fused_cross_entropy(x, labels)))(logits)
    g_dense = jax.grad(lambda x: jnp.sum(_dense_ce(x, labels)))(logits)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_dense),
                               atol=1e-5, rtol=1e-4)
    # ignored row gets exactly zero gradient
    assert np.all(np.asarray(g_fused)[5] == 0.0)


def test_bf16_logits():
    rng = np.random.default_rng(3)
    n, v = 64, 2048
    logits = jnp.asarray(rng.standard_normal((n, v)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    got = fused_cross_entropy(logits, labels)
    want = _dense_ce(logits.astype(jnp.float32), labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=1e-2)
    dx = jax.grad(lambda x: jnp.sum(fused_cross_entropy(x, labels)))(logits)
    assert dx.dtype == jnp.bfloat16


def test_extreme_logits_stable():
    # online softmax must not overflow for large-magnitude logits
    n, v = 16, 2048
    logits = jnp.full((n, v), -3000.0, jnp.float32)
    logits = logits.at[:, 7].set(3000.0)
    labels = jnp.full((n,), 7, jnp.int32)
    got = np.asarray(fused_cross_entropy(logits, labels))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, 0.0, atol=1e-3)


def test_under_jit_and_grad_through_matmul():
    """The bench-realistic composition: h @ W -> fused CE -> grads."""
    rng = np.random.default_rng(4)
    n, d, v = 64, 32, 2048
    h = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    W = jnp.asarray(rng.standard_normal((d, v)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)

    @jax.jit
    def loss_fused(W):
        return jnp.mean(fused_cross_entropy(h @ W, labels))

    def loss_dense(W):
        return jnp.mean(_dense_ce(h @ W, labels))

    np.testing.assert_allclose(float(loss_fused(W)), float(loss_dense(W)),
                               atol=1e-5)
    gf = jax.jit(jax.grad(loss_fused))(W)
    gd = jax.grad(loss_dense)(W)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-5,
                               rtol=1e-4)


def _published_layout(model):
    """The model's state dict as a published checkpoint has it: q_proj /
    k_proj / v_proj and gate_proj / up_proj, sliced out of the stored
    qkv_proj and gate_up_proj."""
    cfg = model.cfg
    nh, kvh, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    m = cfg.intermediate_size
    cuts = {"qkv_proj": (("q_proj", 0, nh * d),
                         ("k_proj", nh * d, (nh + kvh) * d),
                         ("v_proj", (nh + kvh) * d, (nh + 2 * kvh) * d)),
            "gate_up_proj": (("gate_proj", 0, m), ("up_proj", m, 2 * m))}
    out = {}
    for key, t in model.state_dict().items():
        base, _, leaf = key.rpartition(".")
        if leaf in cuts:
            for name, lo, hi in cuts[leaf]:
                out[f"{base}.{name}"] = np.asarray(t.numpy())[..., lo:hi]
        else:
            out[key] = t
    return out


def _tiny_llama(seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import llama as L
    paddle.seed(seed)
    return L.LlamaForCausalLM(L.llama_tiny(use_recompute=False,
                                           num_key_value_heads=2))


def test_llama_published_layout_checkpoint_loads():
    """A checkpoint with q/k/v_proj and gate/up_proj keys is joined into
    the stored layout by set_state_dict (models/llama.py
    _translate_fusion_keys): nothing missing, nothing unexpected, the
    same logits."""
    import paddle_tpu as paddle
    src, dst = _tiny_llama(0), _tiny_llama(1)
    ckpt = _published_layout(src)
    assert not any(k.endswith(("qkv_proj", "gate_up_proj")) for k in ckpt)
    missing, unexpected = dst.set_state_dict(ckpt)
    assert not missing and not unexpected, (missing, unexpected)
    ids = paddle.to_tensor(np.arange(16, dtype=np.int32)[None])
    np.testing.assert_array_equal(np.asarray(dst(ids).numpy(), np.float32),
                                  np.asarray(src(ids).numpy(), np.float32))


def test_llama_incomplete_published_checkpoint_is_reported():
    """q_proj without its k_proj cannot be joined: set_state_dict names
    the keys it could not place and the wide key it did not get."""
    dst = _tiny_llama(1)
    ckpt = _published_layout(_tiny_llama(0))
    del ckpt["model.layers.0.self_attn.k_proj"]
    missing, unexpected = dst.set_state_dict(ckpt)
    assert missing == ["model.layers.0.self_attn.qkv_proj"]
    assert sorted(unexpected) == ["model.layers.0.self_attn.q_proj",
                                  "model.layers.0.self_attn.v_proj"]
