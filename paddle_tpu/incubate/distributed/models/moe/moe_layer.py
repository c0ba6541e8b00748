"""MoELayer (ref: incubate/distributed/models/moe/moe_layer.py:263).

Forward: gate -> dispatch einsum -> vmapped expert FFN (weights stacked
[E, ...], annotated P("ep", ...)) -> combine einsum. The aux loss is
accumulated on the layer (`layer.aux_loss`) for the trainer to add, same
contract as the reference's gate.get_loss.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .....autograd.tape import apply_op
from .....nn import initializer as I
from .....nn.layer.layers import Layer
from .....ops._helpers import to_tensor_like
from .gate import GShardGate, SwitchGate

__all__ = ["MoELayer"]


class MoELayer(Layer):
    """Expert-parallel FFN block.

    Args mirror the reference (moe_layer.py:263): d_model, experts given by
    d_hidden + num_experts (stacked SwiGLU/GeLU FFN), gate name or object,
    recompute handled by the caller.

    The models do NOT use this layer: `models/solar_open2.py` runs
    `paddle_tpu.nn.DroplessMoE` (`nn/layer/moe.py`), which sorts the
    routed pairs by expert and multiplies them in groups over one row
    buffer, is told which experts it holds, and drops nothing. This layer
    keeps the reference's API and its gshard / switch gates, whose
    one-hot `[tokens, experts, capacity]` dispatch drops what exceeds an
    expert's capacity and grows with tokens x experts: fine for the
    sizes its tests use, out of reach at 32768 tokens x 320 experts. It
    was not re-pointed: the gates' capacity semantics (position by
    cumulative count, second choice dropped first, the aux loss) are the
    contract of its tests, and a dropless path has no capacity to drop
    by.
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 gate: str | object = "gshard", capacity_factor: float = 1.5,
                 activation: Optional[Callable] = None,
                 mp_group=None, moe_group=None):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        if isinstance(gate, str):
            gate_cls = {"gshard": GShardGate, "switch": SwitchGate,
                        "naive": SwitchGate}[gate]
            self.gate = gate_cls(d_model, num_experts,
                                 capacity_factor=capacity_factor)
        else:
            self.gate = gate
        self.activation = activation or jax.nn.gelu
        # stacked expert weights [E, ...] sharded over the ep axis
        self.w_up = self.create_parameter(
            (num_experts, d_model, d_hidden),
            default_initializer=I.XavierUniform())
        self.w_down = self.create_parameter(
            (num_experts, d_hidden, d_model),
            default_initializer=I.XavierUniform())
        self.b_up = self.create_parameter((num_experts, d_hidden),
                                          is_bias=True)
        self.b_down = self.create_parameter((num_experts, d_model),
                                            is_bias=True)
        self.w_up.pspec = P("ep", None, None)
        self.w_down.pspec = P("ep", None, None)
        self.b_up.pspec = P("ep", None)
        self.b_down.pspec = P("ep", None)
        self.aux_loss = None

    def forward(self, x):
        """x: [..., d_model] -> same shape; sets self.aux_loss (Tensor)."""
        act = self.activation

        def run(a, gw, wu, bu, wd, bd):
            shape = a.shape
            t = a.reshape(-1, shape[-1])                     # [T, d]
            disp, comb, aux = self.gate.route(t, gw)
            disp = disp.astype(t.dtype)
            comb = comb.astype(jnp.float32)
            # [T,E,C] x [T,d] -> [E,C,d]: the ep all-to-all under GSPMD
            e_in = jnp.einsum("tec,td->ecd", disp, t)

            def ffn(xin, wu_e, bu_e, wd_e, bd_e):
                h = act(xin @ wu_e + bu_e)
                return h @ wd_e + bd_e

            e_out = jax.vmap(ffn)(e_in, wu, bu, wd, bd)      # [E, C, d]
            out = jnp.einsum("tec,ecd->td", comb,
                             e_out.astype(jnp.float32))
            return out.reshape(shape).astype(a.dtype), aux

        xt = to_tensor_like(x)
        out, aux = apply_op(run, xt, self.gate.weight, self.w_up, self.b_up,
                            self.w_down, self.b_down, name="moe_layer",
                            n_outputs=2)
        self.aux_loss = aux
        return out
