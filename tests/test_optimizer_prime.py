"""`Optimizer.prime()` (ISSUE 49): the slots that are missing are made by
ONE compiled program, at the values a fresh optimizer starts from and
placed as their targets are; a slot that exists is not touched and costs
no program. CPU, raw parameters of a few numbers each; (f) borrows the
tiny LLaMA step of `test_step_scopes.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.optimizer as popt
from paddle_tpu.observability import scopes, spans
from paddle_tpu.tensor import Parameter, Tensor

from test_step_scopes import _batch, _step

SHAPES = ((3, 5), (5,))

# class, arguments, {slot: (value, leading axes in front of the target's
# shape)}: what the eager loop this file's issue deleted left on a fresh
# optimizer (each rule run once with a zero gradient and a zero learning
# rate)
CASES = [
    ("sgd", popt.SGD, {}, {}),
    ("sgd-decay", popt.SGD, {"weight_decay": 0.1}, {}),
    ("momentum", popt.Momentum, {}, {"velocity": (0.0, ())}),
    ("momentum-nesterov", popt.Momentum, {"use_nesterov": True},
     {"velocity": (0.0, ())}),
    ("adam", popt.Adam, {}, {"moment1": (0.0, ()), "moment2": (0.0, ())}),
    ("adam-amsgrad", popt.Adam, {"amsgrad": True},
     {"moment1": (0.0, ()), "moment2": (0.0, ()),
      "moment2_max": (0.0, ())}),
    ("adamw", popt.AdamW, {}, {"moment1": (0.0, ()), "moment2": (0.0, ())}),
    # AdamW's own rule has no amsgrad branch: two slots, as before
    ("adamw-amsgrad", popt.AdamW, {"amsgrad": True},
     {"moment1": (0.0, ()), "moment2": (0.0, ())}),
    ("adamax", popt.Adamax, {}, {"moment": (0.0, ()), "inf_norm": (0.0, ())}),
    ("adagrad", popt.Adagrad, {"learning_rate": 0.1},
     {"moment": (0.0, ())}),
    ("adagrad-initial", popt.Adagrad,
     {"learning_rate": 0.1, "initial_accumulator_value": 0.3},
     {"moment": (0.3, ())}),
    ("adadelta", popt.Adadelta, {},
     {"avg_squared_grad": (0.0, ()), "avg_squared_update": (0.0, ())}),
    ("rmsprop", popt.RMSProp, {"learning_rate": 0.1},
     {"mean_square": (0.0, ()), "momentum": (0.0, ())}),
    ("rmsprop-centered", popt.RMSProp,
     {"learning_rate": 0.1, "centered": True, "momentum": 0.9},
     {"mean_square": (0.0, ()), "mean_grad": (0.0, ()),
      "momentum": (0.0, ())}),
    ("lamb", popt.Lamb, {}, {"moment1": (0.0, ()), "moment2": (0.0, ())}),
    ("asgd", popt.ASGD, {"batch_num": 3},
     {"d": (0.0, ()), "ys": (0.0, (3,))}),
    # the step sizes start at the lower end of the range: the rule clips
    # the zero learning rate prime() runs it with
    ("rprop", popt.Rprop, {"learning_rate_range": (1e-4, 10.0)},
     {"prev_grad": (0.0, ()), "lrs": (1e-4, ())}),
]
IDS = [c[0] for c in CASES]


def _params(dtype="float32"):
    rng = np.random.default_rng(7)
    return [Parameter(jnp.asarray(rng.normal(size=s), dtype), name=f"p{i}")
            for i, s in enumerate(SHAPES)]


def _give_masters(opt, params):
    """What `amp.decorate(level="O2")` leaves: float32 masters of bf16
    parameters."""
    for p in params:
        opt._master_weights[id(p)] = p.data.astype(jnp.float32)


def _give_grads(params, seed, times=1.0):
    rng = np.random.default_rng(seed)
    for p in params:
        g = jnp.asarray(rng.normal(size=p.shape), p.data.dtype) * times
        p.grad = Tensor(g, stop_gradient=True)


def _compiles_and_events(fn):
    spans.clear()
    fn()
    ring = spans.ring()
    return (sum(ev["name"] == "xla.backend_compile" for ev in ring),
            [ev for ev in ring if ev["name"] == "optimizer.prime"])


def _bits(opt):
    return {k: np.asarray(v) for k, v in opt._state.items()}


def _same_bits(got, want):
    assert list(got) == list(want)
    for k in want:
        assert got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(got[k], want[k])


# -- (a) a fresh optimizer ---------------------------------------------------

@pytest.mark.parametrize("masters", [False, True],
                         ids=["plain", "multi_precision"])
@pytest.mark.parametrize("_, cls, kw, slots", CASES, ids=IDS)
def test_a_fresh_optimizer_gets_the_slots_the_eager_loop_made(
        _, cls, kw, slots, masters):
    params = _params("bfloat16" if masters else "float32")
    opt = cls(parameters=params, **kw)
    if masters:
        _give_masters(opt, params)
    opt._step_count = 7
    lr = opt.get_lr()
    before = [np.asarray(p.data) for p in params]
    masters_before = {k: np.asarray(v)
                      for k, v in opt._master_weights.items()}
    compiles, events = _compiles_and_events(opt.prime)
    assert list(opt._state) == [(id(p), n) for p in params for n in slots]
    for p in params:
        for name, (value, lead) in slots.items():
            got = opt._state[(id(p), name)]
            assert got.shape == lead + p.data.shape
            assert got.dtype == (jnp.float32 if masters else p.data.dtype)
            np.testing.assert_array_equal(
                np.asarray(got), np.full(got.shape, value, got.dtype))
    assert opt._step_count == 7 and opt.get_lr() == lr
    for p, was in zip(params, before):
        np.testing.assert_array_equal(np.asarray(p.data), was)
    for k, was in masters_before.items():
        np.testing.assert_array_equal(np.asarray(opt._master_weights[k]),
                                      was)
    assert compiles == (1 if slots else 0)
    assert len(events) == (1 if slots else 0)


def test_a_whole_step_rule_returns_quietly():
    opt = popt.LBFGS(parameters=_params())
    compiles, events = _compiles_and_events(opt.prime)
    assert not opt._state and not compiles and not events


def test_frozen_parameters_get_no_slots():
    params = _params()
    params[0].stop_gradient = True
    opt = popt.AdamW(parameters=params)
    opt.prime()
    assert {pid for pid, _ in opt._state} == {id(params[1])}


# -- (b) an optimizer that has state ----------------------------------------

@pytest.mark.parametrize("_, cls, kw, slots", [c for c in CASES if c[3]],
                         ids=[c[0] for c in CASES if c[3]])
def test_existing_slots_keep_every_bit_and_cost_no_program(
        _, cls, kw, slots):
    """One step, then prime(): the update the eager loop ran here with a
    zero gradient multiplied `moment1` by beta1 and `moment2` by beta2."""
    params = _params()
    opt = cls(parameters=params, **kw)
    _give_grads(params, 1)
    opt.step()
    want = _bits(opt)
    assert any(np.any(v != 0) for v in want.values())
    weights = [np.asarray(p.data) for p in params]
    for _ in range(2):
        compiles, events = _compiles_and_events(opt.prime)
        assert not compiles and not events
        _same_bits(_bits(opt), want)
    assert opt._step_count == 1
    for p, was in zip(params, weights):
        np.testing.assert_array_equal(np.asarray(p.data), was)


def test_only_the_missing_slot_is_made_beside_the_ones_restored():
    """State restored from a run without amsgrad: the moments stay, the
    running maximum starts fresh."""
    params = _params()
    donor = popt.Adam(parameters=params)
    _give_grads(params, 2)
    donor.step()
    opt = popt.Adam(parameters=params, amsgrad=True)
    opt.set_state_dict(donor.state_dict())
    want = _bits(opt)
    compiles, events = _compiles_and_events(opt.prime)
    got = _bits(opt)
    for p in params:
        new = got.pop((id(p), "moment2_max"))
        np.testing.assert_array_equal(new, np.zeros_like(new))
    _same_bits(got, want)
    assert compiles == 1 and opt._step_count == 1
    assert events[0]["attrs"] == {"programs": "1", "slots_made": "2",
                                  "slots_kept": "4", "parameters": "2"}


# -- (c) one program, whatever the shapes ------------------------------------

def test_a_dozen_shapes_are_one_program_and_one_event():
    params = [Parameter(jnp.zeros((i + 1, 2 + i % 3), jnp.float32),
                        name=f"w{i}") for i in range(13)]
    assert len({p.data.shape for p in params}) == 13
    opt = popt.AdamW(parameters=params)
    compiles, events = _compiles_and_events(opt.prime)
    assert compiles <= 1
    assert len(opt._state) == 26
    (ev,) = events
    assert ev["setup"] and ev["dur_s"] > 0
    assert ev["attrs"] == {"programs": "1", "slots_made": "26",
                           "slots_kept": "0", "parameters": "13"}
    assert "optimizer.prime" in scopes.SETUP


# -- (d) placement -------------------------------------------------------------

def test_a_new_slot_is_placed_as_its_target_is():
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
    specs = [P("a", "b"), P(None, "b"), P(), P("b")]
    shapes = [(4, 6), (2, 8), (3, 3), (4,)]
    params = [Parameter(jax.device_put(jnp.ones(s, jnp.bfloat16),
                                       NamedSharding(mesh, spec)),
                        name=f"w{i}")
              for i, (s, spec) in enumerate(zip(shapes, specs))]
    opt = popt.ASGD(parameters=params, batch_num=2)
    # a master weight split another way than its parameter: the slot
    # follows the master, which is what its update reads
    master = jax.device_put(jnp.ones((4, 6), jnp.float32),
                            NamedSharding(mesh, P("b", None)))
    opt._master_weights[id(params[0])] = master
    compiles, _ = _compiles_and_events(opt.prime)
    assert compiles == 1
    for p in params:
        target = opt._master_weights.get(id(p), p.data)
        d = opt._state[(id(p), "d")]
        assert d.sharding.is_equivalent_to(target.sharding, d.ndim), p.name
        assert d.dtype == target.dtype and d.committed
        assert opt._state[(id(p), "ys")].shape == (2,) + p.data.shape


# -- under a trace -------------------------------------------------------------

def test_under_a_trace_the_slots_are_made_inline():
    params = _params()
    opt = popt.Adagrad(learning_rate=0.1, parameters=params,
                       initial_accumulator_value=0.5)
    kept = [p.data for p in params]

    def body(ws):
        for p, w in zip(params, ws):
            p.data = w
        try:
            opt.prime()
            return [opt._state[(id(p), "moment")] for p in params]
        finally:
            opt._state.clear()
            for p, w in zip(params, kept):
                p.data = w

    spans.clear()
    out = jax.jit(body)(kept)
    ring = spans.ring()
    assert sum(ev["name"] == "xla.backend_compile" for ev in ring) == 1
    (ev,) = [ev for ev in ring if ev["name"] == "optimizer.prime"]
    assert ev["attrs"]["programs"] == "0"
    for o, w in zip(out, kept):
        np.testing.assert_array_equal(np.asarray(o),
                                      np.full(w.shape, 0.5, w.dtype))


# -- (e) the caller that primes every step -----------------------------------

def test_two_scaled_steps_give_the_moments_of_two_plain_steps():
    plain, scaled = _params(), _params()
    opt_plain = popt.AdamW(learning_rate=0.01, parameters=plain)
    opt_scaled = popt.AdamW(learning_rate=0.01, parameters=scaled)
    scaler = paddle.amp.GradScaler(init_loss_scaling=4.0)
    for seed in (3, 4):
        _give_grads(plain, seed)
        opt_plain.step()
        _give_grads(scaled, seed, times=4.0)
        scaler.step(opt_scaled)
        scaler.update()
    for p, q in zip(plain, scaled):
        for name in ("moment1", "moment2"):
            np.testing.assert_allclose(
                np.asarray(opt_scaled._state[(id(q), name)]),
                np.asarray(opt_plain._state[(id(p), name)]), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(q.data), np.asarray(p.data),
                                   rtol=1e-6)


# -- (f) through TrainStep -------------------------------------------------------

def test_the_first_lower_compiles_next_to_nothing_and_traces_once():
    from chipbench import scope_reduce
    step = _step(False)
    x = _batch(32)
    spans.clear()
    step.lower(x, x)
    ring = spans.ring()
    assert scope_reduce.setup_phases(ring)["inner_compiles"] <= 3
    (ev,) = [ev for ev in ring if ev["name"] == "optimizer.prime"]
    assert ev["within"] == "train_step.lower/train_step.call_args"
    trained = sum(not p.stop_gradient for p in step.model.parameters())
    assert ev["attrs"] == {"programs": "1", "slots_made": str(2 * trained),
                           "slots_kept": "0", "parameters": str(trained)}
    step(x, x)
    step(x, x)
    assert step._traces == 1
