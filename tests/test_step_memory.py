"""The compiled step accounts for its device memory (ISSUE 36): two set-up
events. `train_step.memory` is the compiler's own byte count of the
executable `TrainStep.lower().compile()` made; `train_step.residuals` is
what the forward keeps for the backward, summed by scope and taped op
over the tape's pullbacks while the step traces. CPU: shapes and counts
only, a byte of device memory comes from the chip (PERF.md section 4)."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.autograd import tape
from paddle_tpu.autograd.tape import apply_op
from paddle_tpu.framework import core
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.observability import scopes, spans
from paddle_tpu.tensor import Tensor

SEQ = 32
F32 = 4


def _llama():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=SEQ, dtype="float32"))


def _step(model=None, **kw):
    model = model or _llama()
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    return paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l),
                                **kw)


def _batch(seq=SEQ, rows=2):
    return paddle.to_tensor(np.zeros((rows, seq), np.int32))


def _events(name, ring=None):
    out = []
    for ev in (spans.ring() if ring is None else ring):
        if ev["name"] == name:
            assert ev["setup"] and ev["ev"] == "setup_event"
            out.append(ev)
    return out


def _ledger(ring=None, trace=1):
    """({scope: (bytes, arrays)}, the total's attributes) of one trace."""
    rows = [ev["attrs"] for ev in _events("train_step.residuals", ring)
            if ev["attrs"]["trace"] == str(trace)]
    total = [a for a in rows if a["scope"] == "*"]
    assert len(total) == 1
    return ({a["scope"]: (int(a["bytes"]), int(a["arrays"]))
             for a in rows if a["scope"] != "*"}, total[0])


# -- train_step.memory --------------------------------------------------------

def test_lower_hands_back_jaxs_lowered_behind_a_thin_wrapper():
    step = _step()
    x = _batch()
    low = step.lower(x, x)
    plain = step._compiled.lower(*step._call_args((x, x)))
    assert low.as_text() == plain.as_text()
    assert low.as_text(debug_info=True) == plain.as_text(debug_info=True)
    assert low.cost_analysis() == plain.cost_analysis()
    assert low.out_info == plain.out_info
    assert type(low.compile()) is type(plain.compile())
    with pytest.raises(AttributeError):
        low.no_such_attribute


def test_compile_records_the_compilers_own_count_once():
    step = _step()
    x = _batch()
    spans.clear()
    low = step.lower(x, x)
    assert _events("train_step.memory") == []      # lower() compiles nothing
    compiled = low.compile()
    found = _events("train_step.memory")
    assert len(found) == 1
    ev, mem = found[0], compiled.memory_analysis()
    a = ev["attrs"]
    for ours, theirs in (("argument_bytes", "argument_size_in_bytes"),
                         ("output_bytes", "output_size_in_bytes"),
                         ("alias_bytes", "alias_size_in_bytes"),
                         ("temp_bytes", "temp_size_in_bytes"),
                         ("generated_code_bytes",
                          "generated_code_size_in_bytes")):
        assert int(a[ours]) == getattr(mem, theirs), ours
    assert int(a["temp_bytes"]) > 0
    assert int(a["sum_bytes"]) == (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
        + mem.generated_code_size_in_bytes)
    # the compiler's own peak where it gives one, else the sum
    assert int(a["peak_bytes"]) == (mem.peak_memory_in_bytes
                                    or int(a["sum_bytes"]))
    assert int(a["argument_bytes"]) <= int(a["peak_bytes"]) <= \
        int(a["sum_bytes"])
    assert a["executable"] == step._exec_tag and a["devices"] == "1"
    assert "bytes_limit" not in a           # the CPU's runtime keeps no count
    assert 0 <= ev["dur_s"] < 1.0
    # the donated state (arguments 0-3) is what the outputs alias
    state = sum(v.size * v.dtype.itemsize
                for v in jax.tree_util.tree_leaves(
                    step._call_args((x, x))[:4]))
    assert int(a["alias_bytes"]) == state
    # the step itself records nothing more: no per-step event
    step(x, x)
    step(x, x)
    assert len(_events("train_step.memory")) == 1


def test_memory_under_a_sharding_plan_counts_a_device():
    from paddle_tpu.distributed.sharding import ShardingPlan
    from paddle_tpu.distributed.topology import HybridCommunicateGroup
    hcg = HybridCommunicateGroup(dp_degree=1, sharding_degree=2, mp_degree=2,
                                 devices=jax.devices()[:4])
    plan = ShardingPlan(hcg.mesh, stage=3)
    model = _llama()
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    plan.materialize(model, opt)
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l),
                                shard=plan)
    x = _batch()
    spans.clear()
    compiled = step.lower(x, x).compile()
    a = _events("train_step.memory")[0]["attrs"]
    assert a["devices"] == "4"
    assert int(a["argument_bytes"]) == \
        compiled.memory_analysis().argument_size_in_bytes
    _, total = _ledger()
    assert total["shapes"] == "global"
    one = _step()
    whole = one.lower(x, x).compile().memory_analysis().argument_size_in_bytes
    assert int(a["argument_bytes"]) < 0.6 * whole     # a device's share


# -- train_step.residuals: the walk -------------------------------------------

def _traced(fn, *arrays):
    """Run `fn` on tracers of `arrays` (what a step's body sees)."""
    jax.jit(fn).trace(*arrays)


def test_ledger_of_a_two_layer_toy_equals_a_hand_count():
    B, H, M = 4, 8, 16
    got = {}

    def body(x, w1, w2):
        X = Tensor(x, stop_gradient=True)
        W1, W2 = (Tensor(w, stop_gradient=False) for w in (w1, w2))
        with scopes.scope("mlp"):
            h = apply_op(lambda a, w: jnp.tanh(a @ w), X, W1, name="up")
        y = apply_op(lambda a, w: a @ w, h, W2, name="down")
        loss = apply_op(lambda a: jnp.sum(a * a), y, name="square")
        got["ledger"] = tape.kept_residuals([loss], [x, w1, w2])
        return loss.data

    _traced(body, jnp.ones((B, H)), jnp.ones((H, M)), jnp.ones((M, M)))
    by_key, state_bytes = got["ledger"]
    assert by_key == {
        "mlp:up": [B * M * F32, 1],    # tanh's derivative; x is an input
        "down": [B * M * F32, 1],      # h, for dW2; W2 is an input
        "square": [B * M * F32, 1],    # y
    }
    # x (for dW1) and W2 (for dh) are held but are the step's own; W1 is
    # not needed: x takes no gradient
    assert state_bytes == (B * H + M * M) * F32


def test_checkpointed_op_counts_its_inputs_and_named_kept_values_only():
    B, H = 4, 16
    got = {}

    def block(a, w):
        z = checkpoint_name(a @ w, "kept_here")
        return jnp.tanh(jnp.tanh(z) @ w.T)

    def body(x, w0, w):
        h = apply_op(lambda a, b: a @ b, Tensor(x, stop_gradient=True),
                     Tensor(w0, stop_gradient=False), name="in")
        W = Tensor(w, stop_gradient=False)
        for key, policy in (
                ("nothing", None),
                ("named", jax.checkpoint_policies.save_only_these_names(
                    "kept_here")),
                ("other", jax.checkpoint_policies.save_only_these_names(
                    "not_stamped"))):
            y = apply_op(jax.checkpoint(block, policy=policy), h, W,
                         name="block")
            loss = apply_op(jnp.sum, y, name="sum")
            got[key] = tape.kept_residuals([loss], [x, w0, w])
        return loss.data

    _traced(body, jnp.ones((B, H)), jnp.ones((H, H)), jnp.ones((H, H)))
    act = B * H * F32
    # the block's input h once; two tanh outputs and a product are not kept
    assert got["nothing"][0] == {"block": [act, 1]}
    assert got["named"][0] == {"block": [2 * act, 2]}
    assert got["other"][0] == {"block": [act, 1]}
    # x (for dw0) and w (the block's other input) are the step's own
    assert {s for _, s in got.values()} == {(B * H + H * H) * F32}


def test_parameters_are_state_bytes_not_residuals():
    step = _step()
    x = _batch()
    spans.clear()
    step.lower(x, x)
    by_scope, total = _ledger()
    head = 64 * 96 * F32                       # lm_head, held for dh
    ids = 2 * SEQ * 4                          # the batch, held by the loss
    assert int(total["state_bytes"]) == head + ids
    assert int(total["bytes"]) == sum(b for b, _ in by_scope.values())
    assert int(total["arrays"]) == sum(n for _, n in by_scope.values())
    assert total["shapes"] == "global" and total["executable"] == \
        step._exec_tag
    # the scanned stack keeps its OWN stacked copy of the layers' weights
    # (`jnp.stack` inside the taped op: a real second buffer), two rotary
    # tables and, a layer, its input and the three stamped matmul outputs
    # its backward reads (`llama_mlp_down`'s is read by nobody)
    per_layer = 64 * 128 + 64 * 64 + 64 * 256 + 128 * 64 + 2 * 64
    acts = 2 * SEQ * (64 + 128 + 64 + 128)
    rope = 2 * SEQ * 16
    assert by_scope["decoder_scan"] == (
        (2 * per_layer + 2 * acts + rope) * F32, 12)
    assert by_scope["lm_head"] == (2 * SEQ * 64 * F32, 1)
    assert set(by_scope) == {"decoder_scan", "lm_head", "rms_norm", "embed",
                             "loss:cross_entropy"}


def test_a_delta_rule_layer_keeps_its_input_and_nothing_else():
    """Tiny Solar-Open2 through `TrainStep`: `layers:kda_attention` is one
    layer input a delta-rule layer, one array each: nothing the block's
    own backward recomputes (the norm's output, the shared low-rank
    product, the groups' stacked outputs) leaks into the forward's
    residuals. The weights it hands its backward are the step's own."""
    from paddle_tpu.models.solar_open2 import (SolarOpen2ForCausalLM,
                                               solar_open2_tiny)
    paddle.seed(0)
    cfg = solar_open2_tiny()
    kda_layers = cfg.num_hidden_layers - len(cfg.gqa_layers)
    step = _step(SolarOpen2ForCausalLM(cfg))
    x = _batch()
    spans.clear()
    step.lower(x, x)
    by_scope, _ = _ledger()
    assert kda_layers == 3
    assert by_scope["layers:kda_attention"] == (
        kda_layers * 2 * SEQ * cfg.hidden_size * F32, kda_layers)


def test_an_array_two_ops_keep_counts_once():
    B, H = 4, 8
    got = {}

    def body(x, w):
        h = apply_op(lambda a, b: a @ b, Tensor(x, stop_gradient=True),
                     Tensor(w, stop_gradient=False), name="in")
        # both keep h itself: each multiplies it by the other's operand
        a = apply_op(lambda u: u * u, h, name="first")
        b = apply_op(lambda u: u * u, h, name="second")
        loss = apply_op(lambda u, v: jnp.sum(u + v), a, b, name="sum")
        got["both"] = tape.kept_residuals([loss], [x, w])
        got["one"] = tape.kept_residuals(
            [apply_op(jnp.sum, a, name="sum")], [x, w])
        return loss.data

    _traced(body, jnp.ones((B, H)), jnp.ones((H, H)))
    one = sum(b for b, _ in got["one"][0].values())
    both = sum(b for b, _ in got["both"][0].values())
    assert one == both == B * H * F32
    assert sum(n for _, n in got["both"][0].values()) == 1


# -- train_step.residuals: the events -----------------------------------------

def test_a_retrace_records_a_second_set_under_its_trace_number():
    step = _step()
    x, y = _batch(), _batch(seq=16)
    spans.clear()
    step.lower(x, x)
    step.lower(x, x)               # jax's trace cache: no second trace
    first, _ = _ledger(trace=1)
    assert [ev["attrs"]["trace"] for ev in _events("train_step.residuals")
            ].count("2") == 0
    step.lower(y, y)
    second, total = _ledger(trace=2)
    assert set(second) == set(first)
    assert second["lm_head"][0] * 2 == first["lm_head"][0]
    assert second["decoder_scan"][1] == first["decoder_scan"][1]
    assert total["trace"] == "2" and float(
        _events("train_step.residuals")[-1]["dur_s"]) >= 0


def test_accumulating_step_records_one_ledger_a_trace():
    step = _step(accumulate_steps=2)
    x = _batch(rows=4)
    spans.clear()
    step.lower(x, x)
    by_scope, total = _ledger()
    # a micro-batch's, not the batch's: two rows of the four
    assert by_scope["lm_head"] == (2 * SEQ * 64 * F32, 1)
    assert int(total["state_bytes"]) == 64 * 96 * F32 + 2 * SEQ * 4


class _Attention(Layer):
    """One attention call recorded under `attn/core`, under the remat
    policy the step arms."""

    def __init__(self):
        super().__init__()
        self.w = self.create_parameter([64, 3 * 4 * 64], dtype="float32")

    def loss(self, ids, labels):
        def attend(w):
            a = jnp.ones((1, 128, 64), jnp.float32) @ w
            q, k, v = (t.reshape(1, 128, 4, 64) for t in jnp.split(a, 3, -1))
            return fa.flash_attention_bshd(q, k[:, :, :2], v[:, :, :2],
                                           causal=True)

        with scopes.scope("attn/core"):
            o = apply_op(jax.checkpoint(
                attend, policy=core.current_remat_policy()), self.w,
                name="attend")
        return apply_op(lambda u: jnp.sum(u * u), o, name="square")


def test_keeping_splash_residuals_raises_attn_core_by_kept_bytes_x_calls(
        monkeypatch):
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    ledgers = {}
    for policy in ("nothing", "save_matmul_outputs"):
        step = _step(_Attention(), remat_policy=policy)
        x = _batch()
        step._build()
        spans.clear()
        step._compiled.trace(*step._call_args((x, x)))
        ledgers[policy] = (_ledger()[0], _events("train_step.kept"))
    (lean, none), (kept, events) = (ledgers["nothing"],
                                    ledgers["save_matmul_outputs"])
    assert none == [] and len(events) == 1
    assert events[0]["attrs"]["kept"] == fa.SPLASH_RESIDUALS
    held = len(events) * int(events[0]["attrs"]["bytes"])
    assert held == 1 * 4 * 128 * (64 * F32 + 4)      # out + logsumexp
    assert kept["attn/core:attend"][0] - lean.get(
        "attn/core:attend", (0, 0))[0] == held
    assert kept["square"] == lean["square"]


def test_lowered_text_is_the_same_with_and_without_the_ledger(monkeypatch):
    x = _batch()
    shas = []
    for ledger in (True, False):
        if not ledger:
            monkeypatch.setattr(paddle.jit.TrainStep, "_note_residuals",
                                lambda self, loss, step_inputs: None)
        spans.clear()
        # one call site: with debug_info the text names the caller's line
        text = _step().lower(x, x).as_text(debug_info=True)
        text = re.sub(r"0x[0-9a-f]+|train_step_\d+", "0x", text)
        shas.append(hashlib.sha256(text.encode()).hexdigest())
        assert bool(_events("train_step.residuals")) == ledger
    assert shas[0] == shas[1]


def test_eager_backward_records_nothing():
    model = _llama()
    x = _batch()
    spans.clear()
    loss = model.loss(x, x)
    loss.backward()
    assert all(p.grad is not None for p in model.parameters())
    assert not _events("train_step.residuals")
    assert not _events("train_step.memory")


def test_both_events_are_setup_events_of_the_vocabulary():
    assert {"train_step.memory", "train_step.residuals"} <= set(scopes.SETUP)
    # the flag that printed the runtime's count after a step is gone
    assert paddle.get_flags("FLAGS_log_memory_stats") == {
        "FLAGS_log_memory_stats": None}
