"""A model's forward, or its loss and every gradient, as ONE compiled
program, and its plain reference as another. Run eagerly, a model is one
XLA program an operation (hundreds to compile, each once a shape); the
tape records on tracers under `jax.jit` as it does inside `TrainStep`, so
a parity test compiles what it checks once. The model's state goes in as
arguments (`Layer.use_state`), and the buffers a run writes (counters,
kept losses) are written back as an eager run would leave them."""
import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.tensor import Parameter, Tensor


def shapes_only(make):
    """The model `make()` builds, for a test that reads shapes or program
    text alone: the constructor traced abstractly and zeros put in
    (drawing the weights eagerly, one program a parameter shape, is most
    of a tiny model's seconds)."""
    box = {}
    jax.eval_shape(lambda: box.update(model=make()))
    paddle.seed(0)      # the traced constructor left a tracer as the key
    for t in box["model"].state_dict().values():
        t.data = jnp.asarray(np.zeros(t.data.shape, t.data.dtype))
    return box["model"]


def run(model, fn, *batch):
    """`fn(*tensors)` -> a Tensor or a (nested) tuple, list or dict of
    them, as arrays, under one `jax.jit`."""
    def arrays(tree):
        return jax.tree_util.tree_map(
            lambda t: t.data if isinstance(t, Tensor) else t, tree,
            is_leaf=lambda t: isinstance(t, Tensor))

    def pure(state, *args):
        with model.use_state(state):
            out = arrays(fn(*[Tensor(a) for a in args]))
            written = {k: t.data for k, t in model.state_dict().items()
                       if not isinstance(t, Parameter)}
        return out, written

    sd = model.state_dict()
    out, written = jax.jit(pure)({k: t.data for k, t in sd.items()},
                                 *[jnp.asarray(a) for a in batch])
    for k, v in written.items():
        sd[k].data = v
    return out


def reference(fn, state, ids, *rest):
    """A plain reference `fn(state, ids, *rest)` under one `jit` too: a
    program a call, not one an operation."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda s, i: fn(s, i, *rest))(state, jnp.asarray(ids))


def loss_and_grads(model, loss_of, *batch):
    """(loss, {leaf: its gradient, None where the loss does not reach it})
    of `loss_of(*tensors)`: forward and `backward()` in one program."""
    def fn(*tensors):
        for p in model.parameters():
            p.grad = None
        try:
            loss = loss_of(*tensors)
            loss.backward()
            return loss, {k: p.grad for k, p in model.named_parameters()
                          if p.grad is not None}
        finally:
            for p in model.parameters():
                p.grad = None

    loss, grads = run(model, fn, *batch)
    return float(loss), {k: np.asarray(grads[k]) if k in grads else None
                         for k, _ in model.named_parameters()}
