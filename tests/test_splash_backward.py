"""The splash route's backward in its two forms (ISSUE 50).

`flash_attention.splash_backward` chooses, from a call's shape and mask
alone, between the library's ONE backward kernel (a tile's scores made once
for dq, dk and dv; dq leaves it once an outer kv block and the copies are
summed after) and the two kernels there were. The kernels run through the
Pallas interpreter here: dq, dk and dv of both forms against a float32
reference at the tolerance `test_flash_attention.py` holds the route to,
and the routing function over the seven cells' call shapes as a table.
Which outer blocks the chip's compiler takes is `test_tpu_compile.py`'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.observability import scopes, spans

BF16 = jnp.bfloat16


def _reference(q, k, v, causal, valid, scale):
    """Dense float32 attention, BHSD; `valid` [B, Sk] bool or None."""
    group = q.shape[1] // k.shape[1]
    kr, vr = (jnp.repeat(a.astype(jnp.float32), group, axis=1)
              for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr) * scale
    Sq, Sk = q.shape[2], k.shape[2]
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m = jnp.tril(m)
    m = m[None, None]
    if valid is not None:
        m = m & valid[:, None, None, :]
    s = jnp.where(m, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vr)


# name: (causal, Hq, Hk, Sq, Sk, D, Dv, padding, (bq, bk), outer, partials)
# under a causal mask the outer block is at most Sk / 4; Sk = 1536 divides
# by 512 alone of the listed outer blocks
CASES = {
    "causal-group-of-2-outer-2-blocks": (
        True, 4, 2, 2048, 2048, 64, 64, False, (256, 256), 512, 4),
    "full-group-of-2-outer-4-blocks": (
        False, 4, 2, 1536, 1536, 64, 64, False, (128, 128), 512, 3),
    "causal-values-narrower-256-128-outer-1-block": (
        True, 2, 2, 512, 512, 256, 128, False, (256, 256), 256, 2),
    "causal-segment-ids-outer-4-blocks": (
        True, 4, 2, 2048, 2048, 64, 64, True, (128, 128), 512, 4),
    "full-segment-ids-keys-longer-than-queries": (
        False, 2, 1, 256, 1536, 64, 64, True, (128, 256), 512, 3),
}
# the bound on dq's copies a test sets: none of its own, one that lets one
# kv head's copies through and not two's, one that lets nothing through
FORMS = {"one_kernel": None, "one_kernel-a-kv-head-a-call": "one head",
         "two_kernels": -1}


def _grads(case, dtype=jnp.float32, form="one_kernel", monkeypatch=None):
    """(the routed backward, (loss, (dq, dk, dv)) of the kernels, the same
    of the dense float32 reference)."""
    causal, Hq, Hk, Sq, Sk, D, Dv, padded, blocks, _, partials = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, Hq, Sq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (1, Hk, Sk, D)).astype(dtype)
    v = jax.random.normal(ks[2], (1, Hk, Sk, Dv)).astype(dtype)
    do = jax.random.normal(ks[3], (1, Hq, Sq, Dv))
    valid = (jnp.arange(Sk)[None, :] < Sk - 200) if padded else None
    # rows of q past the valid keys are don't-care under segment ids
    rows = (jnp.arange(Sq) < Sk - 200)[None, None, :, None] \
        if padded and Sq == Sk else 1.0
    scale = 1.0 / np.sqrt(D)
    bound = FORMS[form]
    if bound == "one head":
        bound = partials * (Hq // Hk) * Sq * 128 * jnp.dtype(dtype).itemsize
    if bound is not None:
        monkeypatch.setattr(fa, "_ONE_KERNEL_MAX_PARTIAL_BYTES", bound)
    chosen = fa.splash_backward(q.shape, Hk, Sk, Dv, dtype, causal, None,
                                blocks)

    def kernels(q, k, v):
        o = fa._splash_gqa(q, k, v, causal, scale, valid, interpret=True,
                           blocks=blocks)
        return (o.astype(jnp.float32) * do * rows).sum()

    def dense(q, k, v):
        return (_reference(q, k, v, causal, valid, scale) * do * rows).sum()

    got = jax.jit(jax.value_and_grad(kernels, argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(dense, argnums=(0, 1, 2)))(
        *(a.astype(jnp.float32) for a in (q, k, v)))
    return chosen, got, want


def _cases():
    for case in sorted(CASES):
        for form in FORMS:
            # one kv head a call only where the call holds two
            if form != "one_kernel-a-kv-head-a-call" or (
                    CASES[case][2] == 2 and CASES[case][3] == 2048):
                yield pytest.param(case, form, id=f"{case}-{form}")


@pytest.mark.parametrize("case, form", _cases())
def test_dq_dk_dv_match_the_float32_reference(case, form, monkeypatch):
    """Every form at the tolerance the route is held to today (float32
    operands: dq's copies are float32 and their sum adds no rounding)."""
    chosen, got, want = _grads(case, form=form, monkeypatch=monkeypatch)
    Hk, bk = CASES[case][2], CASES[case][8][1]
    outer, partials = CASES[case][-2:]
    if form == "two_kernels":
        assert chosen == ("two_kernels", bk, 0, 0, Hk)
    else:
        assert chosen[:3] == ("one_kernel", outer, partials), chosen
        assert chosen.kv_heads_a_call == (Hk if form == "one_kernel" else 1)
    assert abs(float(got[0]) - float(want[0])) < 1e-3 * max(
        1.0, abs(float(want[0])))
    for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-3, err_msg=f"{case} {name}")


def test_bf16_copies_of_dq_cost_no_more_than_the_two_kernels(monkeypatch):
    """bf16 operands, four copies of dq each rounded to bf16 and summed in
    float32: the one kernel's dq is no further from the float32 reference
    than the two kernels' (whose dq is rounded once), by the largest gap
    over the largest gradient; dk and dv are the same kernel's."""
    case = "causal-group-of-2-outer-2-blocks"
    _, one, want = _grads(case, BF16)
    chosen, two, _ = _grads(case, BF16, "two_kernels", monkeypatch)
    assert chosen.form == "two_kernels"

    def gap(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.abs(a - b).max() / np.abs(b).max())

    for name, a, b, ref in zip(("dq", "dk", "dv"), one[1], two[1], want[1]):
        assert gap(a, ref) <= 1.5 * gap(b, ref) + 1e-3, name
        assert gap(a, ref) < 0.02, name


# cell: (q [B, Hq, Sq, D], kv heads, Sk, value width, window) of ONE call
# of the cell's step, bf16 -> the routed form, outer block, copies, the
# bytes live at a time, kv heads a kernel call
CELLS = {
    "glm-4.7-flash-ep4.pretrain-16k": (
        ((1, 5, 16384, 256), 5, 16384, 256, None),
        ("one_kernel", 1024, 16, 16 * 5 * 16384 * 256 * 2, 5)),
    "solar-open2-250b-ep40.pretrain-32k": (
        ((1, 8, 32768, 128), 1, 32768, 128, None),
        ("one_kernel", 4096, 8, 8 * 8 * 32768 * 128 * 2, 1)),
    "xing4.0-29b-a4b-ep8.pretrain-4k-batch": (
        ((1, 8, 4096, 256), 8, 4096, 128, None),
        ("one_kernel", 1024, 4, 4 * 8 * 4096 * 256 * 2, 8)),
    "yi-6b-1chip.pretrain": (
        ((1, 32, 4096, 128), 4, 4096, 128, None),
        ("one_kernel", 1024, 4, 4 * 32 * 4096 * 128 * 2, 4)),
    "yi-6b-4chip.pretrain": (
        ((1, 16, 4096, 128), 2, 4096, 128, None),
        ("one_kernel", 1024, 4, 4 * 16 * 4096 * 128 * 2, 2)),
    # 16 copies of all 32 heads x 32768 x 64 (stored 128 lanes wide) would
    # be 4.3 GB: 2 of the 8 kv heads a call hold the bound's 1 GiB
    "granite-4.0-h-micro-pp4.pretrain-32k": (
        ((1, 32, 32768, 64), 8, 32768, 64, None),
        ("one_kernel", 2048, 16, 2 ** 30, 2)),
    # a window of 513 (`LocalMask`): two kernels whatever the shape
    "dots3-note-prev-ep32.pretrain-16k": (
        ((1, 16, 16384, 256), 16, 16384, 128, 513),
        ("two_kernels", 512, 0, 0, 16)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_routing_over_the_cells_call_shapes(cell):
    (q_shape, kv_heads, Sk, dv, window), want = CELLS[cell]
    got = fa.splash_backward(q_shape, kv_heads, Sk, dv, BF16, True, window)
    assert tuple(got) == want
    bs = fa._splash_block_sizes(q_shape[2], Sk, q_shape[3], None, got)
    assert bs.use_fused_bwd_kernel is (got.form == "one_kernel")
    assert bs.block_kv_dkv == got.block_kv_dkv
    assert (bs.block_q, bs.block_kv, bs.block_kv_compute,
            bs.block_kv_dkv_compute) == (512, 512, 512, 512)
    assert bs.block_kv_dq == (None if bs.use_fused_bwd_kernel else 512)


def test_a_call_over_the_bound_keeps_two_kernels():
    """The bound is on the bytes live at a time: a call takes fewer kv
    heads a kernel call until its copies fit, and keeps two kernels where
    ONE kv head's copies do not (64 query heads on one kv head at 32768:
    4.3 GB)."""
    whole = fa.splash_backward((1, 5, 16384, 256), 5, 16384, 256, BF16,
                               True, None)
    assert whole.partial_bytes <= fa._ONE_KERNEL_MAX_PARTIAL_BYTES
    assert whole.kv_heads_a_call == 5
    # eight times GLM's heads: 8 of the 40 a call hold the bound exactly
    assert fa.splash_backward((1, 40, 16384, 256), 40, 16384, 256, BF16,
                              True, None) == (
        "one_kernel", 1024, 16, fa._ONE_KERNEL_MAX_PARTIAL_BYTES, 8)
    assert fa.splash_backward((1, 64, 32768, 128), 1, 32768, 128, BF16,
                              True, None) == ("two_kernels", 512, 0, 0, 1)
    # 64-wide heads are counted at the 128 lanes they are stored in
    assert fa.splash_backward((1, 8, 8192, 64), 8, 8192, 64, BF16, True,
                              None).partial_bytes == 4 * 8 * 8192 * 128 * 2


def test_the_outer_block_by_mask_and_the_sweeps_override():
    """Causal: at most a quarter of Sk (the diagonal's outer blocks run
    their masked tiles too); full: as wide as VMEM takes. `blocks=` sets
    the forward's blocks and the width a product is made at, in both
    forms, and the outer block stays a multiple of it."""
    causal = fa.splash_backward((1, 4, 4096, 128), 4, 4096, 128, BF16,
                                True, None)
    full = fa.splash_backward((1, 4, 4096, 128), 4, 4096, 128, BF16,
                              False, None)
    assert (causal[:3], full[:3]) == (("one_kernel", 1024, 4),
                                      ("one_kernel", 2048, 2))
    got = fa.splash_backward((1, 4, 4096, 128), 4, 4096, 128, BF16, False,
                             None, blocks=(256, 1024))
    assert got[:3] == ("one_kernel", 2048, 2)
    bs = fa._splash_block_sizes(4096, 4096, 128, (256, 1024), got)
    assert (bs.block_q, bs.block_kv, bs.block_kv_compute, bs.block_q_dkv,
            bs.block_kv_dkv_compute, bs.block_kv_dkv) == (
                256, 1024, 1024, 256, 1024, 2048)
    # a sequence no listed outer block divides: the compute block itself
    odd = fa.splash_backward((1, 2, 384, 128), 2, 384, 128, BF16, True,
                             None)
    assert odd[:3] == ("one_kernel", 384, 1)


def test_a_traced_call_leaves_one_event(monkeypatch):
    """`train_step.splash_backward` once a traced call of the route, with
    what `splash_backward` chose; an eager call leaves none (it has no
    program to describe), nor does a call off the splash route."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    q = jnp.zeros((1, 2048, 4, 128), BF16)
    kv = jnp.zeros((1, 2048, 2, 128), BF16)

    def events():
        return [ev["attrs"] for ev in spans.ring()
                if ev["name"] == "train_step.splash_backward"]

    spans.clear()
    jax.make_jaxpr(lambda a, b: (
        fa.flash_attention_bshd(a, b, b, causal=True),
        fa.flash_attention_bshd(a, b, b, causal=True, window=513)))(q, kv)
    assert events() == [
        {"form": "one_kernel", "partials": "4", "block_kv_dkv": "512",
         "partial_bytes": str(4 * 4 * 2048 * 128 * 2),
         "kv_heads_a_call": "2"},
        {"form": "two_kernels", "partials": "0", "block_kv_dkv": "512",
         "partial_bytes": "0", "kv_heads_a_call": "2"}]
    assert "train_step.splash_backward" in scopes.SETUP
    spans.clear()
    jax.make_jaxpr(lambda a: fa.flash_attention_bshd(
        a, a, a, causal=True))(q)            # MHA, 128 wide: jax's flash
    assert events() == []
