"""`kernels/sparse_select_attention.py` (ISSUE 33): the index scores, the
exact top-k, attention over the selected keys with its backward, the heads'
summed probabilities and the indexer's loss. Dense `jax.numpy` route against
equations written here, and every Pallas kernel through the interpreter
against the dense route (all interpreter cases share one shape), the four
tile-walking kernels' live-tile grid against the grid they had before
(`_dsa_parent_grid.py`). Beside
them the window route of `kernels/flash_attention.py`: splash under a
`LocalMask`, values narrower than keys, MHA as groups of one."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import sparse_select_attention as dsa

S, J, D, H, DN, DR, DV, K = 256, 4, 128, 2, 128, 64, 128, 40
SCALE = 1.0 / np.sqrt(DN + DR)
F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def data():
    ks = jax.random.split(jax.random.key(0), 10)
    d = {"qi": jax.random.normal(ks[0], (S, J, D)),
         "ki": jax.random.normal(ks[1], (S, D)),
         "w": jax.random.normal(ks[2], (S, J)) * 0.1,
         "qn": jax.random.normal(ks[3], (H, S, DN)).astype(BF16),
         "qr": jax.random.normal(ks[4], (H, S, DR)).astype(BF16),
         "kn": jax.random.normal(ks[5], (H, S, DN)).astype(BF16),
         "kr": jax.random.normal(ks[6], (S, DR)).astype(BF16),
         "v": jax.random.normal(ks[7], (H, S, DV)).astype(BF16),
         "do": jax.random.normal(ks[8], (H, S, DV)).astype(BF16)}
    d["scores"] = dsa._index_scores_dense(d["qi"], d["ki"], d["w"])
    d["mask"], d["lse_i"] = dsa.select_top_k(d["scores"], K)
    d["o"], d["lse"] = dsa._core_dense(*_qkv(d), d["mask"], SCALE)
    d["psum"] = dsa._head_probs_dense(d["qn"], d["qr"], d["kn"], d["kr"],
                                      d["lse"], d["mask"], SCALE,
                                      jnp.zeros((S, S)))
    return d


def _qkv(d):
    return d["qn"], d["qr"], d["kn"], d["kr"], d["v"]


def _gap(a, b):
    return float(jnp.max(jnp.abs(a.astype(F32) - b.astype(F32))))


# -- index scores ----------------------------------------------------------------

def test_index_scores_are_the_equation(data):
    qi, ki, w = (np.asarray(data[k], np.float64) for k in ("qi", "ki", "w"))
    t, s = 200, 17
    want = sum(w[t, j] * max(qi[t, j] @ ki[s], 0.0) for j in range(J))
    assert float(data["scores"][t, s]) == pytest.approx(want, rel=1e-5)
    assert float(data["scores"][s, t]) < -9e29           # above the diagonal


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jitted"])
def test_index_scores_kernel_in_three_bf16_passes(data, jitted):
    """Jitted too: inside one program XLA drops a float32 -> bfloat16 ->
    float32 round trip, which once turned the low halves into zeros."""
    run = functools.partial(dsa._index_scores_fused, interpret=True)
    got = (jax.jit(run) if jitted else run)(data["qi"], data["ki"],
                                            data["w"])
    seen = np.tril(np.ones((S, S), bool))
    assert bool(jnp.all((got < -9e29) == ~seen))
    gap = jnp.where(seen, got - data["scores"], 0.0)
    # hi.hi + hi.lo + lo.hi of float32 operands: 2^-16 relative a product
    assert float(jnp.max(jnp.abs(gap))) < 2e-4
    one_pass = jnp.einsum(
        "tjs,tj->ts", jax.nn.relu(jnp.einsum(
            "tjd,sd->tjs", data["qi"].astype(BF16).astype(F32),
            data["ki"].astype(BF16).astype(F32))), data["w"])
    assert float(jnp.max(jnp.abs(jnp.where(
        seen, one_pass - data["scores"], 0.0)))) > 20 * 2e-4


# -- the selection ----------------------------------------------------------------

def _sets(mask):
    m = np.asarray(mask)
    return [set(np.nonzero(r)[0].tolist()) for r in m]


def test_selection_is_exactly_the_top_k_of_every_row(data):
    got = _sets(data["mask"])
    for t in range(S):
        kk = min(t + 1, K)
        idx = jax.lax.top_k(data["scores"][t], kk)[1]
        assert got[t] == set(np.asarray(idx).tolist()), t
    assert int(np.asarray(data["mask"]).sum()) == sum(
        min(t + 1, K) for t in range(S))


def test_selection_takes_equal_scores_lowest_position_first(data):
    seen = jnp.tril(jnp.ones((S, S), bool))
    tied = jnp.where(seen, jnp.round(data["scores"] * 2), dsa.NEG)
    mask, _ = dsa.select_top_k(tied, K)
    assert (np.asarray(mask).sum(1) == np.minimum(np.arange(S) + 1, K)).all()
    got = _sets(mask)
    for t in (K - 1, K, K + 1, 100, S - 1):
        idx = jax.lax.top_k(tied[t], min(t + 1, K))[1]
        assert got[t] == set(np.asarray(idx).tolist()), t


def test_selection_a_block_of_rows_at_a_time_is_the_same(data):
    mask, lse = dsa.select_top_k(data["scores"], K, block_rows=64)
    assert bool(jnp.all(mask == data["mask"]))
    np.testing.assert_allclose(lse, data["lse_i"], rtol=1e-6)


def test_top_k_of_the_whole_sequence_is_plain_causal(data):
    mask, lse = dsa.select_top_k(data["scores"], S)
    assert bool(jnp.all((mask != 0) == jnp.tril(jnp.ones((S, S), bool))))
    want = jax.nn.logsumexp(data["scores"], axis=1)     # NEG adds nothing
    np.testing.assert_allclose(lse, want, rtol=1e-6)


def test_selection_log_sum_exp_is_over_the_selected(data):
    keep = np.asarray(data["mask"]) != 0
    sc = np.asarray(data["scores"], np.float64)
    t = 123
    want = np.log(np.exp(sc[t][keep[t]]).sum())
    assert float(data["lse_i"][t]) == pytest.approx(want, rel=1e-6)


# -- attention over the selected keys ---------------------------------------------

def test_dense_core_is_the_equation(data):
    f = lambda k: np.asarray(data[k].astype(F32), np.float64)
    h, t = 1, 77
    keep = np.asarray(data["mask"][t]) != 0
    a = (f("qn")[h, t] @ f("kn")[h].T + f("qr")[h, t] @ f("kr").T) * SCALE
    p = np.exp(a[keep] - a[keep].max())
    want = (p / p.sum()) @ f("v")[h][keep]
    np.testing.assert_allclose(np.asarray(data["o"][h, t].astype(F32)),
                               want, atol=2e-2)
    assert float(data["lse"][h, t]) == pytest.approx(
        a[keep].max() + np.log(p.sum()), rel=1e-5)


def test_core_forward_kernel(data):
    o, lse = dsa._core(*_qkv(data), data["mask"], SCALE, True)
    assert _gap(o, data["o"]) <= 2 ** -6              # one bf16 rounding
    assert _gap(lse, data["lse"]) < 1e-5


@pytest.fixture(scope="module")
def core_grads(data):
    def loss(core):
        return lambda *a: jnp.sum(core(*a)[0].astype(F32)
                                  * data["do"].astype(F32))

    dense = jax.grad(loss(lambda *a: dsa._core_dense(
        *a, data["mask"], SCALE)), argnums=(0, 1, 2, 3, 4))(*_qkv(data))
    fused = jax.grad(loss(lambda *a: dsa._core(
        *a, data["mask"], SCALE, True)), argnums=(0, 1, 2, 3, 4))(*_qkv(data))
    return dict(zip(("qn", "qr", "kn", "kr", "v"), zip(dense, fused)))


@pytest.mark.parametrize("leaf", ["qn", "qr", "kn", "kr", "v"])
def test_core_backward_kernels(core_grads, leaf):
    """dq and dkv kernels against jax's transpose of the dense route; the
    rope key's gradient is summed over the heads inside the kernel."""
    dense, fused = core_grads[leaf]
    assert fused.shape == dense.shape and fused.dtype == dense.dtype
    top = float(jnp.max(jnp.abs(dense.astype(F32))))
    assert _gap(dense, fused) <= 2 ** -7 * max(top, 1.0)


def test_core_stamps_its_residuals_with_the_kept_name(data):
    """Under a checkpoint armed with the name the forward kernel is in the
    gradient's jaxpr once; under the save-nothing policy twice."""
    def calls(policy):
        def f(qn):
            g = jax.checkpoint(lambda q: jnp.sum(dsa._core(
                q, *_qkv(data)[1:], data["mask"], SCALE, True)[0].astype(
                    F32)), policy=policy)
            return g(qn)

        text = str(jax.make_jaxpr(jax.grad(f))(data["qn"]))
        return text.count("name=dsa_core_fwd")

    keep = jax.checkpoint_policies.save_only_these_names(fa.SPLASH_RESIDUALS)
    assert calls(keep) == 1
    assert calls(jax.checkpoint_policies.nothing_saveable) == 2


# -- the indexer's target and loss ------------------------------------------------

def test_head_probabilities_kernel_adds_to_what_it_is_given(data):
    acc = jnp.full((S, S), 0.25, F32)
    got = dsa._head_probs_fused(data["qn"], data["qr"], data["kn"],
                                data["kr"], data["lse"], data["mask"], SCALE,
                                acc, interpret=True)
    assert _gap(got - 0.25, data["psum"]) < 1e-5
    fresh = dsa._head_probs_fused(data["qn"], data["qr"], data["kn"],
                                  data["kr"], data["lse"], data["mask"],
                                  SCALE, None, interpret=True)
    assert _gap(fresh, data["psum"]) < 1e-5      # the first group's call
    np.testing.assert_allclose(data["psum"].sum(1), H, rtol=1e-5)
    assert float(jnp.max(jnp.where(data["mask"] == 0, data["psum"], 0))) == 0


def _kl(qi, ki, w, data):
    keep = data["mask"] != 0
    sc = dsa._index_scores_dense(qi, ki, w)
    logq = jax.nn.log_softmax(jnp.where(keep, sc, -jnp.inf), axis=1)
    p = data["psum"] / H
    return jnp.sum(jnp.where(keep & (p > 0), p * (
        jnp.log(jnp.where(p > 0, p, 1.0)) - logq), 0.0)) / S


@pytest.fixture(scope="module")
def loss_grads(data, request):
    args = (data["qi"], data["ki"], data["w"])
    const = (data["scores"], data["mask"], data["lse_i"], data["psum"], H)
    want = jax.grad(lambda *a: _kl(*a, data), argnums=(0, 1, 2))(*args)
    dense = jax.grad(lambda *a: dsa._indexer_loss(*a, *const, False),
                     argnums=(0, 1, 2))(*args)
    real = dsa._index_bwd_fused
    dsa._index_bwd_fused = lambda *a: real(*a, interpret=True)
    try:
        fused = jax.grad(lambda *a: dsa._indexer_loss(*a, *const, True),
                         argnums=(0, 1, 2))(*args)
    finally:
        dsa._index_bwd_fused = real
    return dict(zip(("qi", "ki", "w"), zip(want, dense, fused)))


def test_indexer_loss_is_the_kl_to_the_heads_mean(data):
    got = dsa.indexer_loss(data["qi"], data["ki"], data["w"], data["scores"],
                           data["mask"], data["lse_i"], data["psum"], H,
                           use_pallas=False)
    assert float(got) == pytest.approx(
        float(_kl(data["qi"], data["ki"], data["w"], data)), rel=1e-5)
    assert float(got) > 0


@pytest.mark.parametrize("leaf", ["qi", "ki", "w"])
def test_indexer_loss_backward(loss_grads, leaf):
    """(softmax_S(I) - p) / S on the selected pairs, kept in bfloat16 from
    the forward (2^-9 a number), pulled back to q^I, k^I and the heads'
    weights: jax's transpose on the dense route, two kernels (operands in
    bfloat16) on the other."""
    want, dense, fused = loss_grads[leaf]
    top = float(jnp.max(jnp.abs(want)))
    assert _gap(want, dense) < 4e-3 * top
    assert _gap(want, fused) < 4e-2 * top


def test_indexer_loss_treats_scores_mask_and_target_as_constants(data):
    g = jax.grad(lambda sc, ps: dsa.indexer_loss(
        data["qi"], data["ki"], data["w"], sc, data["mask"], data["lse_i"],
        ps, H, use_pallas=False), argnums=(0, 1))(data["scores"],
                                                  data["psum"])
    assert all(float(jnp.max(jnp.abs(x))) == 0 for x in g)


# -- the four tile-walking kernels' grid (ISSUE 35) --------------------------------

@pytest.mark.parametrize("by_keys", [False, True], ids=["by-rows", "by-keys"])
@pytest.mark.parametrize("size, bq, bk", [
    (1024, 256, 512), (1024, 512, 512), (2048, 512, 512), (512, 256, 512),
    (256, 256, 256), (384, 128, 128), (1536, 512, 512), (1024, 512, 128)])
def test_live_tiles_are_the_causal_triangle_once(size, bq, bk, by_keys):
    """Every (row block, key block) that holds a pair s <= t is in the
    prefetched lists exactly once and no other; a row block's (a key
    block's) tiles are side by side and ascending, which is what lets a
    kernel initialise at the first and write at the last."""
    rows, keys = dsa._live_tiles(size, bq, bk, by_keys)
    assert rows.dtype == keys.dtype == np.int32
    got = list(zip(rows.tolist(), keys.tolist()))
    want = {(i, j) for i in range(size // bq) for j in range(size // bk)
            if j * bk <= i * bq + bq - 1}
    assert len(got) == len(set(got)) == len(want) and set(got) == want
    assert got == sorted(got, key=(lambda a: a[::-1]) if by_keys else None)
    first = [a for a, b in zip(got, [None] + got[:-1])
             if b is None or a[by_keys] != b[by_keys]]
    if by_keys:                 # what the dkv kernel takes for first, last
        assert all(i == j * bk // bq for i, j in first)
        assert all(a[0] == size // bq - 1 for a, b in zip(
            got, got[1:] + [None]) if b is None or a[1] != b[1])
    else:                       # the forward, dq and the probabilities
        assert all(j == 0 for _, j in first)
        assert all(a[1] == dsa._diag(a[0], bq, bk) for a, b in zip(
            got, got[1:] + [None]) if b is None or a[0] != b[0])


def test_heads_a_step_divide_the_heads_and_fit_the_budget():
    """The most heads that divide the call's and whose blocks fit; a block
    counts in VMEM tiles (a 64-wide bfloat16 row takes 128 lanes, a vector
    of rows eight sublanes)."""
    assert dsa._vmem_bytes((16, 512, 64), BF16) == 16 * 512 * 128 * 2
    assert dsa._vmem_bytes((None, 1, 512), F32) == 8 * 512 * 4
    assert dsa._vmem_bytes((256, 512), jnp.int8) == 256 * 512
    for heads in (1, 2, 12, 16):
        for per_head in (1, 2 << 20, 5 << 20, 50 << 20):
            step = lambda g: (1 << 20) + g * per_head
            G = dsa._heads_per_step(heads, step)
            assert heads % G == 0
            assert G == 1 or step(G) <= dsa._STEP_VMEM
            assert all(heads % g or step(g) > dsa._STEP_VMEM
                       for g in range(G + 1, heads + 1))


LEAVES = ("o", "lse", "dqn", "dqr", "dkn", "dkr", "dv", "psum", "psum_fresh")


def _walked(mod, d, **kw):
    """The four kernels of `mod` through the interpreter on d's arrays."""
    qkv = (d["qn"], d["qr"], d["kn"], d["kr"], d["v"])
    o, lse = mod._core_fwd_fused(*qkv, d["mask"], SCALE, True, **kw)
    dqn, dqr, dkn, dkr, dv = mod._core_bwd_fused(
        *qkv, d["mask"], o, lse, d["do"], SCALE, True, **kw)
    probs = functools.partial(mod._head_probs_fused, *qkv[:4], lse,
                              d["mask"], SCALE, interpret=True, **kw)
    return dict(zip(LEAVES, (o, lse, dqn, dqr, dkn, dkr, dv,
                             probs(jnp.full(d["mask"].shape, 0.25, F32)),
                             probs(None))))


@functools.lru_cache(maxsize=None)
def _case(size):
    """Inputs and the parent's grid over them. 1024: four heads, tiles
    above the diagonal (row blocks 0 and 1 see key block 0 alone: first
    and last tile in one step); 256: `data`'s shape, one tile in all."""
    import _dsa_parent_grid as parent
    heads = 4 if size == 1024 else H
    ks = jax.random.split(jax.random.key(size), 7)
    bf = lambda k, shape: jax.random.normal(k, shape).astype(BF16)
    d = {"qn": bf(ks[0], (heads, size, DN)), "qr": bf(ks[1], (heads, size, DR)),
         "kn": bf(ks[2], (heads, size, DN)), "kr": bf(ks[3], (size, DR)),
         "v": bf(ks[4], (heads, size, DV)), "do": bf(ks[5], (heads, size, DV))}
    scores = jnp.where(jnp.tril(jnp.ones((size, size), bool)),
                       jax.random.normal(ks[6], (size, size)), dsa.NEG)
    d["mask"], _ = dsa.select_top_k(scores, size // 8)
    return d, _walked(parent, d)


@pytest.fixture(scope="module", params=[
    (1024, 1, 256), (1024, 2, 256), (1024, 4, 256), (1024, 4, 512),
    (1024, None, 512), (256, 1, 256), (256, 2, 256)],
    ids=lambda p: "S%d-G%s-rows%d" % p)
def walked(request):
    size, heads, rows = request.param
    d, want = _case(size)
    return rows, want, _walked(dsa, d, heads=heads, rows=rows)


@pytest.mark.parametrize("leaf", LEAVES)
def test_live_tile_walk_against_the_parents_grid(walked, leaf):
    """Whatever the heads a step, a row's online softmax sees the same key
    blocks in the same order: at the parent's 256 rows the forward, dq and
    the summed probabilities are its bits, at 512 rows to 1e-6 (the CPU's
    matmul may block another shape another way). dkv keeps its 256 rows:
    dkn and dv are the parent's bits, the rope key's gradient, summed over
    the heads in another order, its float32 sum to 1e-6: in bfloat16 the
    next number at most, and seldom."""
    rows, want, got = walked
    a, b = want[leaf], got[leaf]
    assert a.shape == b.shape and a.dtype == b.dtype
    top = float(jnp.max(jnp.abs(a.astype(F32))))
    if leaf != "dkr" and (rows == 256 or leaf in ("dkn", "dv")):
        assert bool(jnp.all(a == b))
    elif a.dtype == BF16:
        assert _gap(a, b) <= 2 ** -8 * top
        assert float(jnp.mean(a != b)) < 0.01
    else:
        assert _gap(a, b) <= 1e-6 * top
    if leaf == "psum_fresh":        # nothing but zeros above the diagonal
        assert float(jnp.max(jnp.abs(jnp.triu(b, 1)))) == 0.0
        assert _gap(b + 0.25, got["psum"]) < 1e-6


def test_a_traced_full_layer_takes_no_step_it_skips(monkeypatch):
    """The `dsa.grid` events of a step's trace: each of the four kernels
    was given live tiles x head groups steps and none more."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from paddle_tpu.models.dots3_note import (Dots3NoteForCausalLM,
                                              dots3_note_tiny)
    from paddle_tpu.observability import scopes, spans
    monkeypatch.setattr(dsa, "_on_tpu", lambda: True)
    paddle.seed(0)
    model = Dots3NoteForCausalLM(dots3_note_tiny(num_hidden_layers=2))
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))
    x = paddle.to_tensor(np.zeros((1, 1024), np.int32))
    step._build()
    spans.clear()
    step._compiled.trace(*step._call_args((x, x)))
    events = [ev["attrs"] for ev in spans.ring() if ev["name"] == "dsa.grid"]
    assert {ev["kernel"] for ev in events} == {
        "dsa_core_fwd", "dsa_core_bwd_dq", "dsa_core_bwd_dkv",
        "dsa_head_probs"}
    heads = model.cfg.head_group
    for ev in events:
        n = {k: int(v) for k, v in ev.items() if k != "kernel"}
        assert n["grid_steps"] * n["heads_per_step"] == n["live_tiles"] * heads
        assert n["keys"] == 512 and n["rows"] == (
            256 if ev["kernel"] == "dsa_core_bwd_dkv" else 512)
        # the triangle of 1024 tokens: 3 of 4 tiles at 512 rows, 6 of 8 at 256
        assert n["live_tiles"] == (6 if n["rows"] == 256 else 3)
    assert "dsa.grid" in scopes.SETUP


# -- the window route of flash_attention ------------------------------------------

def _dense_window(q, k, v, window, scale):
    s = jnp.einsum("bthd,bshd->bhts", q.astype(F32), k.astype(F32)) * scale
    T = q.shape[1]
    t, c = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    s = jnp.where((c <= t) & (t - c < window), s, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                      v.astype(F32))


@pytest.fixture(scope="module")
def mha():
    ks = jax.random.split(jax.random.key(3), 3)
    return (jax.random.normal(ks[0], (1, 256, 2, 128)),
            jax.random.normal(ks[1], (1, 256, 2, 128)),
            jax.random.normal(ks[2], (1, 256, 2, 64)))


@pytest.mark.parametrize("window", [33, 256, 1000])
def test_window_route_keys_wider_than_values(mha, window):
    """MHA (heads = kv heads) with a window and values of their own width
    goes through splash as groups of one; a window of the whole sequence
    or more is plain causal attention."""
    q, k, v = mha
    got = fa.flash_attention_bshd(q, k, v, causal=True, window=window,
                                  interpret=True)
    assert got.shape == (1, 256, 2, 64)
    want = _dense_window(q, k, v, window, 1.0 / np.sqrt(128))
    assert _gap(got, want) < 2e-5
    if window >= 256:
        plain = _dense_window(q, k, v, 10 ** 6, 1.0 / np.sqrt(128))
        assert _gap(got, plain) < 2e-5


def test_window_route_backward(mha):
    q, k, v = mha

    def loss(f):
        return lambda q_, k_, v_: jnp.sum(jnp.sin(f(q_, k_, v_)))

    got = jax.grad(loss(lambda *a: fa.flash_attention_bshd(
        *a, causal=True, window=33, interpret=True)), argnums=(0, 1, 2))(
            q, k, v)
    want = jax.grad(loss(lambda *a: _dense_window(
        *a, 33, 1.0 / np.sqrt(128))), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert _gap(a, b) < 5e-5


def test_supported_reads_a_value_width_and_a_window(monkeypatch):
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    q, k = (1, 16384, 16, 256), (1, 16384, 16, 256)
    assert fa.supported(q, k, True)
    assert fa.supported(q, k, True, v_dim=128, window=513)
    assert fa.supported(q, k, True, v_dim=64)
    assert not fa.supported(q, k, True, v_dim=96)
    assert not fa.supported(q, k, False, window=513, has_bias=True)
    assert not fa.supported(q, k, True, v_dim=128, has_bias=True)
    assert not fa.supported((1, 16384, 16, 192), (1, 16384, 16, 192), True)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention_bshd(jnp.zeros((1, 128, 1, 64)),
                                jnp.zeros((1, 128, 1, 64)),
                                jnp.zeros((1, 128, 1, 64)), window=9)


def test_mha_through_splash_is_stamped_and_plain_mha_is_as_it_was(mha):
    """The window (or a value width of its own) sends heads = kv heads
    through splash, whose out and logsumexp carry SPLASH_RESIDUALS; plain
    MHA stays on jax's older flash kernel, unnamed."""
    q, k, v = mha

    def text(**kw):
        f = lambda q_: jnp.sum(fa.flash_attention_bshd(
            q_, k, kw.pop("v", v), causal=True, interpret=True, **kw))
        return str(jax.make_jaxpr(f)(q))

    assert fa.SPLASH_RESIDUALS in text(window=33)
    assert "splash_mqa_fwd" in text(window=33)
    assert fa.SPLASH_RESIDUALS in text()               # values 64, keys 128
    plain = text(v=k)
    assert fa.SPLASH_RESIDUALS not in plain and "splash" not in plain
