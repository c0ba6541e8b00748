"""`distributed.sharding.shard_kernel` on four host devices: every call
form the models write (the norms and the fused cross-entropy: row-wise,
variance tracking on; swiglu and attention: split over mp, the plain
transpose) gives the unsharded call's outputs and gradients, and the
set-up event `shard_kernel.calls` says which sums each trace left out.
Kernels go through the Pallas interpreter where they have that route;
what the chip's compiler makes of the same calls is in
tests/test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from paddle_tpu.distributed.sharding import kernel_mesh_guard, shard_kernel
from paddle_tpu.kernels import cross_entropy as ce
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import fused_norm_residual as fnr
from paddle_tpu.kernels import rms_norm as rn
from paddle_tpu.kernels import swiglu as sg
from paddle_tpu.models.llama import _swiglu
from paddle_tpu.observability import scopes, spans

H = 128


def _rand(shape, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _mesh(kind):
    devs = np.asarray(jax.devices())
    if kind == "mp_alone":
        return Mesh(devs[:2], ("mp",))
    return Mesh(devs[:4].reshape(2, 2), ("sharding", "mp"))


# each form: fn(batch, interpret) -> (sharded call, plain call, operands,
# argnums of the operands that take a gradient), with the specs its call
# site writes (models/llama.py, nn/functional/loss.py). The plain call
# goes through the Pallas interpreter where the kernel has that route; so
# does the sharded call, except where its rows vary over a mesh axis: the
# interpreter (jax 0.9) evaluates a kernel's body primitive by primitive
# and refuses one whose operands' variance differs ("Primitive div
# requires varying manual axes to match"), which a compiled Mosaic call
# never meets. There the sharded call takes the kernel's jnp route.
def _rms_norm(B, interpret):
    rows = P("data", None, None)

    def plain(x, w):
        return rn.rms_norm(x, w, 1e-6)   # jnp off the chip, either way

    def sharded(x, w):
        return shard_kernel(plain, (rows, P(None)), rows, batch=B)(x, w)

    return sharded, plain, (_rand((B, 8, H), 0), 1 + .1 * _rand((H,), 1)), \
        (0, 1)


def _fused_add_rms_norm(B, interpret):
    bsh = P("data", None, None)

    def call(use_pallas):
        return lambda r, d, w: fnr.fused_add_rms_norm(r, d, w, 1e-6,
                                                      use_pallas)

    def sharded(r, d, w):
        return shard_kernel(call(interpret), (bsh, bsh, P(None)), (bsh, bsh),
                            batch=B)(r, d, w)

    return sharded, call(True), (_rand((B, 8, H), 0), _rand((B, 8, H), 1),
                                 1 + .1 * _rand((H,), 2)), (0, 1, 2)


def _fused_cross_entropy(B, interpret):
    N, V = 8 * B + (B % 2), 256          # an odd batch: odd rows too
    labels = jax.random.randint(jax.random.PRNGKey(1), (N,), 0, V)
    labels = labels.at[1].set(-100)

    def plain(x, y):
        return ce.fused_cross_entropy(x, y, -100)

    def jnp_route(x, y):
        picked = jnp.take_along_axis(jax.nn.log_softmax(x, axis=-1),
                                     jnp.maximum(y, 0)[:, None], axis=-1)
        return jnp.where(y != -100, -picked[:, 0], 0.0)

    def sharded(x, y):
        return shard_kernel(plain if interpret else jnp_route,
                            (P("data", None), P("data")), P("data"),
                            batch=N)(x, y)

    return sharded, plain, (_rand((N, V), 0), labels), (0,)


def _swiglu_form(B, interpret):
    return _swiglu, sg.swiglu, (_rand((B, 8, H), 0),
                                .05 * _rand((H, 512), 1)), (0, 1)


def _attention(B, interpret):
    bshd = P("data", None, "mp", None)

    def plain(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True, interpret=True)

    def sharded(q, k, v):
        return shard_kernel(plain, (bshd,) * 3, bshd, batch=B,
                            heads=2)(q, k, v)

    return sharded, plain, (_rand((B, 128, 4, 64), 0),
                            _rand((B, 128, 2, 64), 1),
                            _rand((B, 128, 2, 64), 2)), (0, 1, 2)


FORMS = {"rms_norm": _rms_norm, "fused_add_rms_norm": _fused_add_rms_norm,
         "fused_cross_entropy": _fused_cross_entropy,
         "swiglu": _swiglu_form, "attention": _attention}
# mesh, batch: ZeRO x TP as the four-chip cell has it; mp alone (no data
# axis to resolve to); a batch the data axis does not divide (the "data"
# role unresolved: every operand enters replicated)
LAYOUTS = {"sharding2_mp2": ("2x2", 4), "mp_alone": ("mp_alone", 4),
           "data_unresolved": ("2x2", 3)}


@pytest.fixture
def swiglu_through_the_interpreter(monkeypatch):
    monkeypatch.setattr(sg, "_FORCE_PALLAS", True)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("form", FORMS)
def test_sharded_call_matches_the_unsharded_call(
        form, layout, swiglu_through_the_interpreter):
    kind, B = LAYOUTS[layout]
    mesh = _mesh(kind)
    # rows vary over a mesh axis only where the data role resolves
    sharded, plain, args, argnums = FORMS[form](
        B, interpret=layout != "sharding2_mp2")

    def loss(fn):
        def f(*a):
            outs = jax.tree_util.tree_leaves(fn(*a))
            # a different cotangent at every element of every output
            return sum(jnp.sum(o * jnp.cos(jnp.arange(o.size, dtype=o.dtype)
                                           .reshape(o.shape)))
                       for o in outs)
        return f

    def armed(*a):
        with kernel_mesh_guard(mesh):
            return sharded(*a)

    spans.clear()
    want = jax.jit(jax.value_and_grad(loss(plain), argnums))(*args)
    got = jax.jit(jax.value_and_grad(loss(armed), argnums))(*args)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)
    (event,) = [ev["attrs"] for ev in spans.ring()
                if ev["name"] == "shard_kernel.calls"]
    row_wise = form in ("rms_norm", "fused_add_rms_norm",
                        "fused_cross_entropy")
    unsummed = {"sharding2_mp2": "mp", "mp_alone": "mp",
                "data_unresolved": "sharding+mp"}[layout]
    assert event == {"mapped": "1", "tracked": str(int(row_wise)),
                     "unsummed": unsummed if row_wise else ""}


def test_one_event_a_trace_counts_every_mapped_call():
    """Two norms and a swiglu under one armed mesh: three calls mapped,
    the two row-wise ones tracked, each leaving mp unsummed; no mesh
    armed, or a mesh of one device: `fn` itself and no event."""
    mesh = _mesh("2x2")
    norm, _, (x, w), _ = _rms_norm(4, False)
    wgu = .05 * _rand((H, 512), 1)

    def layer(x_, w_, wgu_):
        with kernel_mesh_guard(mesh):
            return norm(_swiglu(norm(x_, w_), wgu_)[..., :H], w_)

    spans.clear()
    jax.jit(jax.grad(lambda *a: jnp.sum(layer(*a)))).lower(x, w, wgu)
    (event,) = [ev["attrs"] for ev in spans.ring()
                if ev["name"] == "shard_kernel.calls"]
    assert event == {"mapped": "3", "tracked": "2", "unsummed": "mp,mp"}
    assert "shard_kernel.calls" in scopes.SETUP

    spans.clear()
    fn = object()
    assert shard_kernel(fn, (P("data"),), P("data"), batch=4) is fn
    with kernel_mesh_guard(Mesh(np.asarray(jax.devices()[:1]), ("mp",))):
        assert shard_kernel(fn, (P("data"),), P("data"), batch=4) is fn
    assert not [ev for ev in spans.ring()
                if ev["name"] == "shard_kernel.calls"]


def test_a_row_wise_call_sums_only_the_weight_gradient():
    """What the backward of a norm holds on sharding 2 x mp 2: one sum,
    of dw over the data axis. The parent summed dx over mp too (two equal
    copies) and dw over both axes."""
    mesh = _mesh("2x2")
    norm, _, (x, w), _ = _rms_norm(4, False)

    def f(x_, w_):
        with kernel_mesh_guard(mesh):
            return jnp.sum(norm(x_, w_) ** 2)

    text = str(jax.make_jaxpr(jax.grad(f, (0, 1)))(x, w))
    sums = [ln.strip() for ln in text.splitlines() if "psum" in ln]
    assert len(sums) == 1 and "axes=('sharding',)" in sums[0] \
        and f"f32[{H}]" in sums[0], sums
