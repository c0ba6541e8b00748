"""LLaMA family — the north-star model (BASELINE.md config 3).

TPU-native design (not a port of any torch/paddle modeling file):
  * RMSNorm + RoPE + SwiGLU, GQA-capable attention via the Pallas flash
    kernel (paddle_tpu/kernels/flash_attention.py)
  * every parameter carries a PartitionSpec annotation (`p.pspec`) encoding
    its tensor-parallel layout over the `mp` axis; ShardingPlan composes
    these with FSDP (`sharding`) placement (SURVEY §2.5 TP+ZeRO mapping)
  * per-layer `jax.checkpoint` (remat) replaces the reference's
    recompute meta-optimizer (fleet/meta_optimizers/recompute)
Reference anchors (behavioral parity targets, not sources):
  fleet/layers/mpu/mp_layers.py:46,335,542 (parallel layers),
  incubate fused_rms_norm / fused_rope kernels.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..autograd.tape import apply_op
from ..framework import core
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.common import Dropout, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..observability.scopes import scope
from ..ops import manipulation as M
from ..ops._helpers import to_tensor_like
from ..tensor import Tensor

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama_tiny",
           "llama_350m", "llama_1b", "llama_7b"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_recompute: bool = True
    # scan_layers: run the decoder stack as ONE lax.scan over stacked
    # per-layer weights — O(1) HLO size instead of O(L) unrolled layers,
    # cutting XLA compile time ~L-fold with identical numerics (and the
    # standard trick for large-L TPU LLMs)
    scan_layers: bool = True
    # Megatron-style sequence parallelism: residual-stream activations are
    # sharded along seq over the `mp` axis between TP blocks (ref
    # fleet/utils/sequence_parallel_utils.py); GSPMD derives the
    # all-gather/reduce-scatter pairs from the annotations
    sequence_parallel: bool = False
    dtype: str = "bfloat16"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self):
        return self.num_key_value_heads or self.num_attention_heads


def _tp_all_reduce():
    """distributed.sharding.tp_all_reduce (imported late, as shard_kernel
    is): the scope of a matmul whose tensor-parallel all-reduce the
    partitioner puts in."""
    from ..distributed.sharding import tp_all_reduce
    return tp_all_reduce()


def _param(layer, shape, pspec, std=0.02, init=None, dtype=None):
    p = layer.create_parameter(
        shape, dtype=dtype,
        default_initializer=init or I.Normal(0.0, std))
    p.pspec = pspec
    return p


class LlamaRMSNorm(Layer):
    def __init__(self, hidden, eps):
        super().__init__()
        self.eps = eps
        self.weight = _param(self, (hidden,), P(None), init=I.Constant(1.0),
                             dtype="float32")

    def forward(self, x):
        from ..distributed.sharding import shard_kernel
        from ..kernels import rms_norm as krn

        def norm(a, w):
            rows = P("data", *[None] * (a.ndim - 1))
            with scope("norm"):
                return shard_kernel(
                    lambda a_, w_: krn.rms_norm(a_, w_, self.eps),
                    (rows, P(None)), rows, batch=a.shape[0])(a, w)

        return apply_op(norm, to_tensor_like(x), self.weight,
                        name="rms_norm")


class LlamaAttention(Layer):
    """Column-parallel qkv, row-parallel o (ref mp_layers.py:335,542 layout,
    expressed as GSPMD specs instead of explicit collectives). q, k and v
    are stored as ONE [h, (nh + 2 kvh) d] projection: the K=hidden
    contraction underuses the MXU at small N, and one wide matmul runs
    markedly faster than three narrow ones."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        nh, kvh = cfg.num_attention_heads, cfg.kv_heads
        self.qkv_proj = _param(self, (h, (nh + 2 * kvh) * d), P(None, "mp"))
        self.o_proj = _param(self, (nh * d, h), P("mp", None))

    def forward(self, x, position_ids=None, kv_cache=None):
        cfg = self.cfg
        B = x.shape[0]
        nh, kvh, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim

        def _core(q, k, v):
            from ..kernels import flash_attention as fa
            # GQA/MQA is native in the kernel wrapper (splash MQA mode —
            # no materialized kv repeat); dense fallback broadcasts
            with scope("attn/core"):
                if fa.supported(q.shape, k.shape, True):
                    from ..distributed.sharding import shard_kernel
                    bshd = P("data", None, "mp", None)
                    return shard_kernel(
                        lambda q_, k_, v_: fa.flash_attention_bshd(
                            q_, k_, v_, causal=True),
                        (bshd, bshd, bshd), bshd, batch=B,
                        heads=kvh)(q, k, v)
                if kvh != nh:
                    rep = nh // kvh
                    k = jnp.repeat(k, rep, axis=2)
                    v = jnp.repeat(v, rep, axis=2)
                return _sdpa(q, k, v)

        def _out(o, wo):
            with scope("attn/out"), _tp_all_reduce():    # row-parallel
                return o.reshape(B, -1, nh * d) @ wo

        # fused QKV+RoPE prologue: one wide projection, rope on the q/k
        # slices in-register (kernels/rope.py), matmul outputs stamped
        # for the save_only_these_names remat policy (jit.TrainStep
        # remat_policy=)
        def attn(a, wqkv, wo):
            from jax.ad_checkpoint import checkpoint_name
            from ..kernels.rope import fused_qkv_rope
            # projection and rotation are ONE fused prologue: both
            # carry attn/qkv
            with scope("attn/qkv"), _tp_all_reduce():
                q, k, v = fused_qkv_rope(a, wqkv, nh, kvh, d,
                                         base=cfg.rope_theta)
            o = _core(q, k, v)
            return checkpoint_name(_out(o, wo), "llama_attn_o")

        return apply_op(attn, to_tensor_like(x), self.qkv_proj,
                        self.o_proj, name="llama_attn_fused")


def _sdpa(q, k, v):
    d = q.shape[-1]
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = qt @ jnp.swapaxes(kt, -1, -2) / math.sqrt(d)
    Sq, Sk = s.shape[-2], s.shape[-1]
    mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.swapaxes(p @ vt, 1, 2).astype(q.dtype)


class LlamaMLP(Layer):
    """SwiGLU; gate | up stored as one column-parallel [h, 2m] projection,
    down row-parallel."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, m = cfg.hidden_size, cfg.intermediate_size
        self.gate_up_proj = _param(self, (h, 2 * m), P(None, "mp"))
        self.down_proj = _param(self, (m, h), P("mp", None))

    def forward(self, x):
        # blockwise Pallas SwiGLU: the [T, 2M] gate/up tensor never hits
        # HBM (kernels/swiglu.py); outputs stamped for the
        # save_only_these_names remat policy
        def mlp(a, wgu, wd):
            from jax.ad_checkpoint import checkpoint_name
            with scope("mlp"):
                o = checkpoint_name(_swiglu(a, wgu), "llama_swiglu")
                with _tp_all_reduce():                   # row-parallel
                    return checkpoint_name(o @ wd, "llama_mlp_down")

        return apply_op(mlp, to_tensor_like(x), self.gate_up_proj,
                        self.down_proj, name="llama_mlp_fused")


def _swiglu(a, wgu):
    """kernels/swiglu on a [B, ..., H] activation. Under a sharded step the
    kernel runs per device (shard_kernel): batch over the data axes, the
    intermediate dim over mp. w_gate_up is [H, gate | up], so a column
    split would hand one device gate columns only — it enters as
    [H, 2, M], split on M, and each device folds its [H, 2, M/mp] back
    into a local gate | up layout."""
    from ..distributed.sharding import shard_kernel
    from ..kernels.swiglu import swiglu
    H, m = wgu.shape[0], wgu.shape[1] // 2
    lead = ("data",) + (None,) * (a.ndim - 2)
    run = shard_kernel(
        lambda a_, w3: swiglu(a_, w3.reshape(H, -1)),
        (P(*lead, None), P(None, None, "mp")),
        P(*lead, "mp"), batch=a.shape[0], heads=m // 128)
    with scope("tp/relayout"):
        w3 = wgu.reshape(H, 2, m)
    return run(a, w3)


class LlamaDecoderLayer(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = LlamaRMSNorm(cfg.hidden_size,
                                                     cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)
        self.use_recompute = cfg.use_recompute
        self.sequence_parallel = cfg.sequence_parallel

    def forward(self, x, position_ids=None):
        if not self.sequence_parallel:
            # the residual add + post-attention RMSNorm collapse into one
            # Pallas pass that emits BOTH the summed stream h and the
            # normalized a2 (kernels/fused_norm_residual)
            from ..distributed.sharding import shard_kernel
            from ..kernels.fused_norm_residual import fused_add_rms_norm
            attn_out = self.self_attn(self.input_layernorm(x), position_ids)
            eps = self.post_attention_layernorm.eps
            bsh = P("data", None, None)

            def add_norm(r, dlt, w):
                with scope("norm"):
                    return shard_kernel(
                        lambda r_, d_, w_: fused_add_rms_norm(r_, d_, w_,
                                                              eps),
                        (bsh, bsh, P(None)), (bsh, bsh),
                        batch=r.shape[0])(r, dlt, w)

            a2, h = apply_op(
                add_norm, to_tensor_like(x), attn_out,
                self.post_attention_layernorm.weight,
                n_outputs=2, name="fused_add_rms_norm")
            return h + self.mlp(a2)
        # sequence parallelism: the residual stream is sharded along seq
        # between the TP blocks, so add and norm stay separate operations
        # the partitioner can place
        from ..distributed.fleet.utils.sequence_parallel_utils import scatter
        x = scatter(x)
        h = x + self.self_attn(self.input_layernorm(x), position_ids)
        h = h + self.mlp(self.post_attention_layernorm(h))
        return scatter(h)


class LlamaModel(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _param(self, (cfg.vocab_size, cfg.hidden_size),
                                   P("mp", None), dtype=cfg.dtype)
        self.layers = LayerList([LlamaDecoderLayer(cfg)
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        if cfg.dtype != "float32":
            self.to(dtype=cfg.dtype)
            # keep norms in fp32 (standard TPU recipe)
            for lyr in self.sublayers(include_self=True):
                if isinstance(lyr, LlamaRMSNorm):
                    lyr.weight.data = lyr.weight.data.astype(jnp.float32)

    def forward(self, input_ids, position_ids=None):
        def embed(ids, w):
            with scope("embed"):
                return jnp.take(w, ids.astype(jnp.int32), axis=0)

        x = apply_op(embed, to_tensor_like(input_ids), self.embed_tokens,
                     name="embed")
        if self.cfg.scan_layers and position_ids is None:
            x = _scan_stack(list(self.layers), x,
                            use_remat=self.cfg.use_recompute)
        elif self.cfg.use_recompute:
            x = _recompute_stack(self.layers, x, position_ids)
        else:
            for lyr in self.layers:
                x = lyr(x, position_ids)
        return self.norm(x)


def _scan_stack(layers, x, use_remat=True):
    """One lax.scan over the (homogeneous) decoder layers: per-layer
    weights are stacked [L, ...] inside the traced fn so autograd tracks
    every individual Parameter; the body runs the template layer once.
    jax.checkpoint on the body == per-layer remat (recompute)."""
    template = layers[0]
    named = list(template.named_parameters())
    objs = [p for _, p in named]
    n_per = len(named)
    all_params = [p for lyr in layers for _, p in lyr.named_parameters()]

    def run(a, *ws):
        def body(h, pl):
            with _swap_param_data(objs, pl):
                return _call_pure(template, h), None

        # policy=None is jax.checkpoint's own default (save nothing);
        # TrainStep(remat_policy=) arms save_only_these_names over the
        # checkpoint_name-stamped matmul outputs via the core context
        b = jax.checkpoint(body, policy=core.current_remat_policy()) \
            if use_remat else body
        # `layers`: the stack's own plumbing (stacking weights, slicing
        # a layer's out, writing its gradients back) has a name too
        with scope("layers"):
            stacks = [jnp.stack(ws[i::n_per]) for i in range(n_per)]
            h, _ = jax.lax.scan(b, a, tuple(stacks))
        return h

    return apply_op(run, x, *all_params, name="decoder_scan")


def _recompute_stack(layers, x, position_ids):
    """Per-layer jax.checkpoint through the tape: each decoder layer's
    forward is wrapped so residuals are rematerialized in backward
    (replaces fleet recompute pass; ref recompute meta-optimizer)."""
    for lyr in layers:
        params = [p for _, p in lyr.named_parameters()]

        def run(a, *ws, _lyr=lyr, _params=params):
            with _swap_param_data(_params, ws), scope("layers"):
                return _call_pure(_lyr, a)

        ckpt = jax.checkpoint(run, policy=core.current_remat_policy())
        x = apply_op(ckpt, x, *params, name="decoder_layer_ckpt")
    return x


import contextlib


@contextlib.contextmanager
def _swap_param_data(params, arrays):
    saved = [p.data for p in params]
    try:
        for p, a in zip(params, arrays):
            p.data = a
        yield
    finally:
        for p, s in zip(params, saved):
            p.data = s


def _call_pure(layer, a):
    """Run a Layer on a raw array with the tape disabled, return raw array."""
    with core.no_grad_guard():
        out = layer(Tensor(a))
    return out.data


def _translate_fusion_keys(sd):
    """Join a checkpoint in the published layout (q_proj / k_proj / v_proj
    and gate_proj / up_proj keys) into the stored one (qkv_proj,
    gate_up_proj). A q_proj without its k_proj and v_proj, or a gate_proj
    without its up_proj, is left as it came: set_state_dict then reports
    it as unexpected and the wide key it should have made as missing."""
    def _arr(v):
        return v.data if isinstance(v, Tensor) else jnp.asarray(v)

    out = dict(sd)
    for key in sd:
        base, _, leaf = key.rpartition(".")
        if leaf == "q_proj":
            parts, wide = (key, f"{base}.k_proj", f"{base}.v_proj"), \
                f"{base}.qkv_proj"
        elif leaf == "gate_proj":
            parts, wide = (key, f"{base}.up_proj"), f"{base}.gate_up_proj"
        else:
            continue
        if all(k in sd for k in parts):
            out[wide] = jnp.concatenate([_arr(sd[k]) for k in parts],
                                        axis=-1)
            for k in parts:
                del out[k]
    return out


def _head(a, w):
    with scope("head"), _tp_all_reduce():                # column-parallel
        return a @ w


def _head_tied(a, w):
    with scope("head"), _tp_all_reduce():
        return a @ jnp.swapaxes(w, 0, 1)


class LlamaForCausalLM(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = _param(self, (cfg.hidden_size, cfg.vocab_size),
                                  P(None, "mp"), dtype=cfg.dtype)
        else:
            self.lm_head = None

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Loads a checkpoint in the stored or the published layout: q/k/v
        and gate/up keys are joined into qkv_proj / gate_up_proj."""
        state_dict = _translate_fusion_keys(dict(state_dict))
        return super().set_state_dict(state_dict, use_structured_name)

    load_dict = set_state_dict
    set_dict = set_state_dict

    def forward(self, input_ids, position_ids=None):
        h = self.model(input_ids, position_ids)
        if self.lm_head is not None:
            return apply_op(_head, h, self.lm_head, name="lm_head")
        return apply_op(_head_tied, h, self.model.embed_tokens,
                        name="lm_head_tied")

    def loss(self, input_ids, labels):
        """Shifted next-token CE in f32 (fused logsumexp path)."""
        logits = self(input_ids)
        B, S, V = logits.shape
        with scope("loss"):
            lg = M.reshape(logits[:, :-1, :], [-1, V])
            lb = M.reshape(labels[:, 1:], [-1])
            return F.cross_entropy(lg, lb, ignore_index=-100)

    # -- decode path (prefill + compiled greedy/sampling scan) --------------
    def generate(self, input_ids, max_new_tokens=32, max_length=None,
                 eos_token_id=None, do_sample=False, temperature=1.0,
                 top_k=0, seed=0, use_cache=True):
        """KV-cache generation: ONE compiled prefill + ONE compiled decode
        scan (ref: analysis_predictor Run -> fused_multi_transformer decode;
        VERDICT r1 item 7). Greedy when do_sample=False. Returns the
        generated ids [B, max_new_tokens] as a Tensor."""
        import numpy as np

        cfg = self.cfg
        ids = input_ids.data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        B, T0 = ids.shape
        if max_length is not None:
            # total-length cap (paddle/HF semantics)
            max_new_tokens = min(max_new_tokens, max(int(max_length) - T0, 1))
        S_max = T0 + max_new_tokens
        state = {k: t.data for k, t in self.state_dict().items()}
        L, kvh, d = cfg.num_hidden_layers, cfg.kv_heads, cfg.head_dim
        cdtype = state["model.embed_tokens"].dtype
        cache_k = jnp.zeros((L, B, S_max, kvh, d), cdtype)
        cache_v = jnp.zeros((L, B, S_max, kvh, d), cdtype)
        eos = -1 if eos_token_id is None else int(eos_token_id)

        # compiled prefill/decode cached per static config
        sig = (B, T0, S_max, max_new_tokens, do_sample, float(temperature),
               int(top_k), eos)
        if not hasattr(self, "_gen_compiled"):
            self._gen_compiled = {}
        if sig in self._gen_compiled:
            prefill, decode = self._gen_compiled[sig]
            return self._run_generate(prefill, decode, state, ids, cache_k,
                                      cache_v, max_new_tokens, do_sample,
                                      temperature, top_k, seed)

        @jax.jit
        def prefill(state, ids, ck, cv):
            logits, ck, cv = _forward_with_cache(
                state, cfg, ids, ck, cv, jnp.zeros((B,), jnp.int32))
            return logits[:, -1], ck, cv

        @jax.jit
        def decode(state, first_tok, ck, cv, key):
            def pick(logits, key):
                if do_sample:
                    lg = logits / max(temperature, 1e-6)
                    if top_k:
                        kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
                        lg = jnp.where(lg < kth, -jnp.inf, lg)
                    return jax.random.categorical(key, lg).astype(jnp.int32)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def step(carry, _):
                tok, ck, cv, cur, done, key = carry
                key, sub = jax.random.split(key)
                logits, ck, cv = _forward_with_cache(
                    state, cfg, tok[:, None], ck, cv, cur)
                nxt = pick(logits[:, -1], sub)
                nxt = jnp.where(done, eos if eos >= 0 else 0, nxt)
                done = done | (nxt == eos)
                return (nxt, ck, cv, cur + 1, done, key), nxt

            # the FIRST sampled token may already be EOS
            done0 = (first_tok == eos) if eos >= 0 else jnp.zeros((B,), bool)
            cur0 = jnp.full((B,), T0, jnp.int32)
            (_, _, _, _, _, _), toks = jax.lax.scan(
                step, (first_tok, ck, cv, cur0, done0, key),
                None, length=max_new_tokens - 1)
            return toks                                  # [N-1, B]

        self._gen_compiled[sig] = (prefill, decode)
        return self._run_generate(prefill, decode, state, ids, cache_k,
                                  cache_v, max_new_tokens, do_sample,
                                  temperature, top_k, seed)

    def _run_generate(self, prefill, decode, state, ids, cache_k, cache_v,
                      max_new_tokens, do_sample, temperature, top_k, seed):
        last_logits, cache_k, cache_v = prefill(state, ids, cache_k, cache_v)
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        if do_sample:
            lg = last_logits / max(temperature, 1e-6)
            if top_k:
                kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
                lg = jnp.where(lg < kth, -jnp.inf, lg)
            first = jax.random.categorical(sub, lg).astype(jnp.int32)
        else:
            first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        if max_new_tokens == 1:
            out = first[:, None]
        else:
            rest = decode(state, first, cache_k, cache_v, key)
            out = jnp.concatenate([first[:, None],
                                   jnp.swapaxes(rest, 0, 1)], axis=1)
        return Tensor(out, stop_gradient=True)


# ---------------------------------------------------------------------------
# generation: prefill + decode as two compiled functions with a KV cache
# (ref: the reference's decode path — fused_multi_transformer_op.cu +
#  masked_multihead_attention / block (paged) multi-head attention kernels,
#  driven by analysis_predictor Run. TPU-native: the whole greedy loop is
#  ONE lax.scan inside jit; the cache is a functional carry.)
# ---------------------------------------------------------------------------


def _gather_layer_weights(state, cfg):
    """Stack per-layer weights [L, ...] from a state dict for lax.scan."""
    L = cfg.num_hidden_layers
    return {n: jnp.stack([state[f"model.layers.{i}.{n}"] for i in range(L)])
            for n in ("input_layernorm.weight",
                      "post_attention_layernorm.weight",
                      "self_attn.qkv_proj", "self_attn.o_proj",
                      "mlp.gate_up_proj", "mlp.down_proj")}


def _block_with_cache(cfg, h, wl, ck, cv, pos_ids, cache_mask):
    """One decoder layer over tokens at pos_ids with a KV cache.

    h: [B, T, H]; ck/cv: [B, S_max, kvh, d] (this layer's cache);
    pos_ids: [B, T] absolute positions; cache_mask: [B, S_max] bool — which
    cache slots are valid AFTER this step's keys are written.
    Returns (h_out, ck_new, cv_new).
    """
    from ..kernels.rms_norm import rms_norm
    from ..kernels.rope import fused_qkv_rope
    from ..kernels.swiglu import swiglu

    B, T = h.shape[0], h.shape[1]
    nh, kvh, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    a = rms_norm(h, wl["input_layernorm.weight"], cfg.rms_norm_eps)
    max_pos = max(cfg.max_position_embeddings, ck.shape[1])
    q, k, v = fused_qkv_rope(a, wl["self_attn.qkv_proj"], nh, kvh, d,
                             position_ids=pos_ids, base=cfg.rope_theta,
                             seq_len=max_pos)
    # write new keys/values into the cache at their absolute positions
    oh = jax.nn.one_hot(pos_ids, ck.shape[1], dtype=ck.dtype)  # [B,T,S_max]
    ck = ck * (1 - oh.sum(1)[:, :, None, None]) + jnp.einsum(
        "bts,btkd->bskd", oh, k.astype(ck.dtype))
    cv = cv * (1 - oh.sum(1)[:, :, None, None]) + jnp.einsum(
        "bts,btkd->bskd", oh, v.astype(cv.dtype))
    if T == 1:
        # decode step: paged-KV attention kernel (Pallas on TPU, dense
        # fallback elsewhere) — block-table layout over the cache pool,
        # ref block_multihead_attention / masked_multihead_attention
        from ..kernels.paged_attention import decode_attention
        lengths = (pos_ids[:, 0] + 1).astype(jnp.int32)  # incl. this token
        o = decode_attention(q, ck, cv, lengths,
                             scale=1.0 / math.sqrt(d))
        o = o.astype(h.dtype).reshape(B, T, nh * d)
    else:
        if kvh != nh:
            rep = nh // kvh
            kk = jnp.repeat(ck, rep, axis=2)
            vv = jnp.repeat(cv, rep, axis=2)
        else:
            kk, vv = ck, cv
        s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                       kk.astype(jnp.float32)) / math.sqrt(d)
        causal = pos_ids[:, :, None] >= jnp.arange(
            ck.shape[1])[None, None, :]
        valid = causal & cache_mask[:, None, :]      # [B, T, S_max]
        s = jnp.where(valid[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", p, vv.astype(jnp.float32))
        o = o.astype(h.dtype).reshape(B, T, nh * d)
    h = h + o @ wl["self_attn.o_proj"]
    a2 = rms_norm(h, wl["post_attention_layernorm.weight"],
                  cfg.rms_norm_eps)
    up = swiglu(a2, wl["mlp.gate_up_proj"])
    return h + up @ wl["mlp.down_proj"], ck, cv


def _forward_with_cache(state, cfg, ids, cache_k, cache_v, cur_len):
    """ids: [B, T] new tokens (T=prompt at prefill, 1 at decode);
    cache_k/v: [L, B, S_max, kvh, d]; cur_len: [B] int32 tokens already
    cached. Returns (logits[B, T, V], cache_k, cache_v)."""
    from ..kernels.rms_norm import rms_norm

    B, T = ids.shape
    S_max = cache_k.shape[2]
    emb = state["model.embed_tokens"]
    h = jnp.take(emb, ids.astype(jnp.int32), axis=0)
    pos_ids = cur_len[:, None] + jnp.arange(T)[None, :]          # [B, T]
    cache_mask = jnp.arange(S_max)[None, :] < (cur_len + T)[:, None]
    wls = _gather_layer_weights(state, cfg)

    def body(carry, xs):
        h = carry
        wl, ck, cv = xs
        h, ck, cv = _block_with_cache(cfg, h, wl, ck, cv, pos_ids,
                                      cache_mask)
        return h, (ck, cv)

    h, (cache_k, cache_v) = jax.lax.scan(
        body, h, (wls, cache_k, cache_v))
    h = rms_norm(h, state["model.norm.weight"], cfg.rms_norm_eps)
    if "lm_head" in state:
        logits = h @ state["lm_head"]
    else:
        logits = h @ jnp.swapaxes(emb, 0, 1)
    return logits.astype(jnp.float32), cache_k, cache_v


# ---------------------------------------------------------------------------
# paged-KV decode: one token per slot over a shared page POOL + block table
# (ref: block_multihead_attention_kernel.cu block_tables decode and the
#  reference's paged serving path — here the pool is a global
#  [L, kvh, n_pages, page, d] array in the Pallas paged_attention layout and
#  the block table maps each slot to its allocated page list; writes are
#  one-token scatters, so XLA updates pages in place under donation.)
# ---------------------------------------------------------------------------


def _block_paged(cfg, h, wl, kp, vp, pos_ids, pg, off, page_table, lens):
    """One decoder layer for a single-token decode over the page pool.

    h: [B, 1, H]; kp/vp: [kvh, P, page, d] (this layer's page pool);
    pos_ids: [B, 1]; pg/off: i32[B] page id + in-page offset for this
    token's KV write; page_table: i32[B, ppmax]; lens: [B] tokens cached
    BEFORE this step.
    """
    from ..kernels.paged_attention import paged_decode_attention
    from ..kernels.rms_norm import rms_norm
    from ..kernels.rope import fused_qkv_rope
    from ..kernels.swiglu import swiglu

    B = h.shape[0]
    nh, kvh, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    a = rms_norm(h, wl["input_layernorm.weight"], cfg.rms_norm_eps)
    max_pos = max(cfg.max_position_embeddings,
                  page_table.shape[1] * kp.shape[2])
    q, k, v = fused_qkv_rope(a, wl["self_attn.qkv_proj"], nh, kvh, d,
                             position_ids=pos_ids, base=cfg.rope_theta,
                             seq_len=max_pos)
    # scatter this token's k/v into page (pg[b], off[b]) — a B-element
    # scatter, not a cache rewrite
    kp = kp.at[:, pg, off].set(jnp.moveaxis(k[:, 0], 1, 0).astype(kp.dtype))
    vp = vp.at[:, pg, off].set(jnp.moveaxis(v[:, 0], 1, 0).astype(vp.dtype))
    o = paged_decode_attention(q[:, 0], kp, vp,
                               (lens + 1).astype(jnp.int32), page_table,
                               scale=1.0 / math.sqrt(d))
    o = o.astype(h.dtype).reshape(B, 1, nh * d)
    h = h + o @ wl["self_attn.o_proj"]
    a2 = rms_norm(h, wl["post_attention_layernorm.weight"],
                  cfg.rms_norm_eps)
    up = swiglu(a2, wl["mlp.gate_up_proj"])
    return h + up @ wl["mlp.down_proj"], kp, vp


def _decode_step_paged(state, cfg, toks, k_pool, v_pool, page_table, lens,
                       active):
    """One decode token for every slot over the shared page pool.

    toks: i32[B]; k/v_pool: [L, kvh, P, page, d]; page_table: i32[B, ppmax]
    (page ids per slot, unused entries 0 = scratch); lens: i32[B] tokens
    already cached; active: bool[B]. Inactive slots write to the scratch
    page and their logits are ignored by the caller.
    Returns (logits[B, V] for the new token, k_pool, v_pool)."""
    from ..kernels.rms_norm import rms_norm

    B = toks.shape[0]
    emb = state["model.embed_tokens"]
    h = jnp.take(emb, toks.astype(jnp.int32), axis=0)[:, None]
    lens = jnp.where(active, lens, 0)
    pos_ids = lens[:, None]
    page = k_pool.shape[3]
    pg = jnp.take_along_axis(page_table, (lens // page)[:, None], axis=1)[:, 0]
    pg = jnp.where(active, pg, 0)                    # scratch for inactive
    off = lens % page
    wls = _gather_layer_weights(state, cfg)

    def body(h, xs):
        wl, kp, vp = xs
        h, kp, vp = _block_paged(cfg, h, wl, kp, vp, pos_ids, pg, off,
                                 page_table, lens)
        return h, (kp, vp)

    h, (k_pool, v_pool) = jax.lax.scan(body, h, (wls, k_pool, v_pool))
    h = rms_norm(h, state["model.norm.weight"], cfg.rms_norm_eps)
    if "lm_head" in state:
        logits = h @ state["lm_head"]
    else:
        logits = h @ jnp.swapaxes(emb, 0, 1)
    return logits.astype(jnp.float32)[:, 0], k_pool, v_pool


# ---------------------------------------------------------------------------
# ragged mixed-phase step: prefill CHUNKS and single-token decodes packed
# into ONE call over the page pool (ref: "Ragged Paged Attention", arxiv
# 2604.15464 — the chunked-prefill continuous-batching step. Rows are
# packed [T] with per-sequence (q_start, q_len, kv_len) metadata; each
# layer scatters the rows' KV into their pages, then one ragged paged
# attention covers every phase in the same kernel invocation.)
# ---------------------------------------------------------------------------


def _block_ragged(cfg, h, wl, kp, vp, pos, page_ids, offs, page_table,
                  q_start, q_len, kv_len):
    """One decoder layer over packed ragged rows against the page pool.

    h: [T, H] packed rows; kp/vp: [kvh, P, page, d] (this layer's pool);
    pos: i32[T] absolute positions; page_ids/offs: i32[T] page id +
    in-page offset for each row's KV write (padding rows carry page 0 =
    scratch); page_table: i32[B, ppmax]; q_start/q_len/kv_len: i32[B]
    per-sequence row metadata (kv_len INCLUDES this step's rows).
    """
    from ..kernels.ragged_paged_attention import ragged_paged_attention
    from ..kernels.rms_norm import rms_norm
    from ..kernels.rope import fused_qkv_rope
    from ..kernels.swiglu import swiglu

    T = h.shape[0]
    nh, kvh, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    a = rms_norm(h, wl["input_layernorm.weight"], cfg.rms_norm_eps)
    max_pos = max(cfg.max_position_embeddings,
                  page_table.shape[1] * kp.shape[2])
    q, k, v = fused_qkv_rope(a, wl["self_attn.qkv_proj"], nh, kvh, d,
                             position_ids=pos, base=cfg.rope_theta,
                             seq_len=max_pos)
    # ONE T-row page scatter per layer (prefill chunks and decode tokens
    # alike); duplicate scratch-page writes from padding rows are benign
    kp = kp.at[:, page_ids, offs].set(jnp.moveaxis(k, 1, 0).astype(kp.dtype))
    vp = vp.at[:, page_ids, offs].set(jnp.moveaxis(v, 1, 0).astype(vp.dtype))
    o = ragged_paged_attention(q, kp, vp, q_start, q_len, kv_len,
                               page_table, scale=1.0 / math.sqrt(d))
    h = h + o.astype(h.dtype).reshape(T, nh * d) @ wl["self_attn.o_proj"]
    a2 = rms_norm(h, wl["post_attention_layernorm.weight"],
                  cfg.rms_norm_eps)
    up = swiglu(a2, wl["mlp.gate_up_proj"])
    return h + up @ wl["mlp.down_proj"], kp, vp


def _ragged_step_paged(state, cfg, toks, pos, k_pool, v_pool, page_ids,
                       offs, page_table, q_start, q_len, kv_len,
                       verify_rows=None):
    """Mixed prefill-chunk + decode rows in ONE call over the page pool.

    toks/pos/page_ids/offs: i32[T] packed rows (padding rows: token 0,
    page 0); k/v_pool: [L, kvh, P, page, d]; page_table: i32[B, ppmax];
    q_start/q_len/kv_len: i32[B]. Returns (last_logits[B, V], k_pool,
    v_pool) where last_logits[b] is the logits at each sequence's LAST
    packed row (garbage for q_len == 0 slots — callers mask).

    verify_rows=K (speculation armed): returns logits for each
    sequence's LAST min(K, q_len) packed rows instead ([B, K, V],
    right-aligned: slot K-1 is the last row, K-1-j the j-th from the
    end; short sequences duplicate their first row in the unused
    leading slots — callers mask). The engine verifies draft tokens
    against the greedy argmax at each draft's own position without
    paying lm-head for every prefill-chunk row in the packed batch."""
    from ..kernels.rms_norm import rms_norm

    T = toks.shape[0]
    emb = state["model.embed_tokens"]
    h = jnp.take(emb, toks.astype(jnp.int32), axis=0)        # [T, H]
    wls = _gather_layer_weights(state, cfg)

    def body(h, xs):
        wl, kp, vp = xs
        h, kp, vp = _block_ragged(cfg, h, wl, kp, vp, pos, page_ids, offs,
                                  page_table, q_start, q_len, kv_len)
        return h, (kp, vp)

    h, (k_pool, v_pool) = jax.lax.scan(body, h, (wls, k_pool, v_pool))
    h = rms_norm(h, state["model.norm.weight"], cfg.rms_norm_eps)
    # rank-3 matmul on purpose (both branches): XLA CPU's rank-2 bf16
    # gemm accumulates differently than the batched form every other
    # decode path uses, which flips greedy argmax at bf16 logit ties
    # (engine parity bar). The per-row branch keeps the SAME batched
    # shape so row logits are bitwise-equal to what the last-row branch
    # would produce for the same row — speculative verification must
    # not flip ties the non-speculative engine resolves the other way
    if verify_rows:
        K = int(verify_rows)
        B = q_start.shape[0]
        j = jnp.arange(K)
        rows = q_start[:, None] + jnp.maximum(
            q_len[:, None] - K + j[None, :], 0)
        rows = jnp.clip(rows, 0, T - 1)
        h_rows = h[rows].reshape(B * K, 1, h.shape[-1])       # [B*K, 1, H]
        if "lm_head" in state:
            logits = h_rows @ state["lm_head"]
        else:
            logits = h_rows @ jnp.swapaxes(emb, 0, 1)
        return (logits.astype(jnp.float32).reshape(B, K, -1),
                k_pool, v_pool)
    last = jnp.clip(q_start + q_len - 1, 0, T - 1)
    h_last = h[last][:, None]                                 # [B, 1, H]
    if "lm_head" in state:
        logits = h_last @ state["lm_head"]
    else:
        logits = h_last @ jnp.swapaxes(emb, 0, 1)
    return logits.astype(jnp.float32)[:, 0], k_pool, v_pool


def llama_tiny(**kw):
    return LlamaConfig(vocab_size=1024, hidden_size=256, intermediate_size=688,
                       num_hidden_layers=2, num_attention_heads=4,
                       max_position_embeddings=512, **kw)


def llama_350m(**kw):
    return LlamaConfig(vocab_size=32000, hidden_size=1024,
                       intermediate_size=2816, num_hidden_layers=24,
                       num_attention_heads=16, **kw)


def llama_1b(**kw):
    return LlamaConfig(vocab_size=32000, hidden_size=2048,
                       intermediate_size=5504, num_hidden_layers=22,
                       num_attention_heads=16, **kw)


def llama_7b(**kw):
    return LlamaConfig(vocab_size=32000, hidden_size=4096,
                       intermediate_size=11008, num_hidden_layers=32,
                       num_attention_heads=32, **kw)
