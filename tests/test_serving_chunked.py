"""Chunked-prefill continuous batching (ISSUE 7): scheduler parity,
mixed-phase packing, token-granular pool accounting, preempt/resume
determinism, the FLAGS_ragged_attention kill switch, and serving
telemetry through the observability registry."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture(autouse=True)
def _disarm_metrics():
    yield
    obs.enable(False)


def _tiny_model(seed=0, **kw):
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=128, use_recompute=False,
                      **kw)
    return LlamaForCausalLM(cfg)


def _reference_generate(model, prompt, n_new):
    out = model.generate(paddle.to_tensor(np.array([prompt], np.int32)),
                         max_new_tokens=n_new, do_sample=False)
    return [int(t) for t in np.asarray(out.numpy())[0][:n_new]]


def _drain(eng, cap=2000):
    n = 0
    while eng.has_work and n < cap:
        eng.step()
        n += 1
    assert not eng.has_work, "engine failed to drain"
    return n


class TestChunkedPrefill:
    def test_multi_tick_prefill_exact_parity(self):
        """A prompt longer than max_chunk_tokens streams in over several
        ticks and still produces the exact isolated-greedy output —
        chunked prefill is a scheduling change, not a numerics change."""
        model = _tiny_model()
        prompt = list(range(3, 21))              # 18 tokens
        ref = _reference_generate(model, prompt, 6)
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       max_chunk_tokens=4)
        assert eng._ragged
        eng.add_request(GenerationRequest(prompt, max_new_tokens=6))
        eng.step()
        # after one tick only one chunk is in KV: prefill is streaming
        assert eng.slots[0].pending and eng.slots[0].length == 4
        _drain(eng)
        assert eng.finished[0].output == ref

    def test_chunk_boundary_straddles_page(self):
        """Chunk size coprime with the page size: chunks straddle page
        boundaries and the per-token page/offset mapping must hold."""
        model = _tiny_model()
        prompt = list(range(1, 40))              # 39 tokens, pages of 16
        ref = _reference_generate(model, prompt, 5)
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       max_chunk_tokens=7)
        eng.add_request(GenerationRequest(prompt, max_new_tokens=5))
        _drain(eng)
        assert eng.finished[0].output == ref

    def test_prefill_packs_with_decode_same_tick(self):
        """A long prompt arriving mid-decode rides the SAME compiled step
        as the decoding slot: one ragged invocation carries decode rows
        plus a prefill chunk (no prefill/decode phase barrier), and the
        decoding user keeps producing a token every tick."""
        model = _tiny_model()
        a = GenerationRequest([5, 17], max_new_tokens=20)
        b = GenerationRequest(list(range(1, 25)), max_new_tokens=4)
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       max_chunk_tokens=8)
        eng.add_request(a)
        for _ in range(3):
            eng.step()
        out_before = len(a.output)
        eng.add_request(b)
        mixed_ticks = 0
        while b.output == [] and eng.has_work:
            eng.step()
            if eng.last_packed_tokens > 1:
                mixed_ticks += 1
            # the decoding slot advances EVERY tick while b prefills
        assert mixed_ticks >= 3                  # 24 tokens / 8 per chunk
        assert len(a.output) >= out_before + mixed_ticks
        _drain(eng)
        assert a.output == _reference_generate(model, a.prompt, 20)
        assert b.output == _reference_generate(model, b.prompt, 4)

    def test_one_compiled_shape_total(self):
        """The ragged regime compiles ONE step (fixed packed bucket) no
        matter how prompt lengths vary — the bucketed regime's per-
        (bucket, k) prefill compiles are gone."""
        model = _tiny_model()
        eng = ContinuousBatchingEngine(model, max_batch=4, max_seq=64,
                                       max_chunk_tokens=16)
        for n in (2, 9, 17, 30):
            eng.add_request(GenerationRequest(list(range(1, n + 1)),
                                              max_new_tokens=3))
        _drain(eng)
        assert eng._compiled_prefill == {}
        assert eng._compiled_ragged is not None
        assert len(eng.finished) == 4

    def test_gqa_chunked_parity(self):
        paddle.seed(3)
        cfg = LlamaConfig(vocab_size=96, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128, use_recompute=False)
        model = LlamaForCausalLM(cfg)
        prompt = list(range(2, 15))
        ref = _reference_generate(model, prompt, 5)
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       max_chunk_tokens=4)
        eng.add_request(GenerationRequest(prompt, max_new_tokens=5))
        _drain(eng)
        assert eng.finished[0].output == ref

    def test_token_granular_pool_accounting(self):
        """Pages are funded chunk by chunk: mid-prefill the slot holds
        only the pages its streamed tokens need, never the whole
        prompt's worth up front."""
        model = _tiny_model()
        prompt = list(range(1, 41))              # 40 tokens = 3 pages
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       max_chunk_tokens=8, total_pages=9)
        eng.add_request(GenerationRequest(prompt, max_new_tokens=2))
        eng.step()                               # first 8-token chunk
        assert len(eng.slot_pages[0]) == 1       # not ceil(40/16)=3
        eng.step()
        assert len(eng.slot_pages[0]) == 1       # 16 tokens still 1 page
        eng.step()
        assert len(eng.slot_pages[0]) == 2
        _drain(eng)
        assert eng.pool.n_free == eng.pool.n_pages - 1


class TestChunkedPreemption:
    def test_preempt_resume_exact_under_tiny_pool(self):
        model = _tiny_model()
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       total_pages=5, max_chunk_tokens=8)
        reqs = [GenerationRequest([11, 5], max_new_tokens=38),
                GenerationRequest([7, 19], max_new_tokens=38)]
        for r in reqs:
            eng.add_request(r)
        _drain(eng)
        assert len(eng.finished) == 2
        assert eng.preemptions >= 1
        for r in reqs:
            assert r.output == _reference_generate(model, r.prompt, 38)

    def test_prefill_parked_pool_preempts_for_progress(self):
        """Two long prompts on a pool that can't hold both: the later
        admission is preempted so the head streams through; both still
        finish with exact outputs."""
        model = _tiny_model()
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       total_pages=4, max_chunk_tokens=16)
        reqs = [GenerationRequest(list(range(1, 34)), max_new_tokens=3),
                GenerationRequest(list(range(2, 35)), max_new_tokens=3)]
        for r in reqs:
            eng.add_request(r)
        _drain(eng)
        assert len(eng.finished) == 2
        for r in reqs:
            assert r.output == _reference_generate(model, r.prompt, 3), \
                (eng.preemptions, r.prompt)

    def test_scheduler_determinism(self):
        """Two engines fed the same workload tick identically: same
        per-tick packed sizes, same preemption count, same outputs."""
        def run():
            model = _tiny_model(seed=1)
            eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                           total_pages=6,
                                           max_chunk_tokens=8)
            for i in range(4):
                eng.add_request(GenerationRequest(
                    list(range(1 + i, 14 + i)), max_new_tokens=10))
            packed = []
            while eng.has_work:
                eng.step()
                packed.append(eng.last_packed_tokens)
            return packed, eng.preemptions, \
                [r.output for r in eng.finished]

        p1, n1, o1 = run()
        p2, n2, o2 = run()
        assert p1 == p2 and n1 == n2 and o1 == o2


class TestKillSwitch:
    def test_flag_off_restores_bucketed_engine(self):
        """FLAGS_ragged_attention=0 restores the legacy engine exactly:
        bucketed prefill compiles come back, the ragged step never
        compiles, and outputs are token-identical to the ragged
        regime's (greedy)."""
        model = _tiny_model()
        prompts = [[9, 4, 2], list(range(1, 14)), [3, 3, 5, 8]]

        def run(**kw):
            eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                           prefill_buckets=(8, 16), **kw)
            reqs = [GenerationRequest(list(p), max_new_tokens=6)
                    for p in prompts]
            for r in reqs:
                eng.add_request(r)
            _drain(eng)
            return eng, [r.output for r in reqs]

        paddle.set_flags({"FLAGS_ragged_attention": False})
        try:
            legacy, legacy_out = run()
        finally:
            paddle.set_flags({"FLAGS_ragged_attention": True})
        ragged, ragged_out = run()
        assert not legacy._ragged and ragged._ragged
        assert legacy._compiled_ragged is None
        assert legacy._compiled_prefill          # bucketed path ran
        assert ragged._compiled_prefill == {}
        assert ragged_out == legacy_out          # token-identical
        for p, out in zip(prompts, legacy_out):
            assert out == _reference_generate(model, p, 6)

    def test_explicit_kwarg_overrides_flag(self):
        model = _tiny_model()
        eng = ContinuousBatchingEngine(model, ragged=False)
        assert not eng._ragged
        eng2 = ContinuousBatchingEngine(model, ragged=True)
        assert eng2._ragged

    def test_zero_chunk_budget_rejected_at_construction(self):
        """max_chunk_tokens < 1 would preempt-thrash forever in
        _schedule_chunks — it must fail fast instead."""
        model = _tiny_model()
        with pytest.raises(ValueError, match="max_chunk_tokens"):
            ContinuousBatchingEngine(model, max_chunk_tokens=0)


class TestServingTelemetry:
    def test_ttft_tpot_pages_preemptions_recorded(self):
        from paddle_tpu.observability import metrics
        model = _tiny_model()
        # the registry is the process's: under xdist an armed test of
        # another file may have counted requests on this worker before
        metrics.reset()
        obs.enable(True)
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       total_pages=5, max_chunk_tokens=8)
        for i in range(2):
            eng.add_request(GenerationRequest([11 + i, 5], max_new_tokens=38))
        _drain(eng)
        snap = metrics.snapshot()

        def agg(hist_id):
            # the SLO layer (default armed, ISSUE 10) labels TTFT/TPOT
            # by priority — aggregate across label cells
            cells = snap["histograms"][hist_id].values()
            return (sum(c["count"] for c in cells),
                    sum(c["sum"] for c in cells))

        ttft = agg("serving.ttft_seconds")
        tpot = agg("serving.tpot_seconds")
        packed = snap["histograms"]["serving.packed_tokens_per_tick"][""]
        assert ttft[0] == 2 and ttft[1] > 0
        assert tpot[0] == 2 and tpot[1] > 0
        assert 1 <= packed["count"] <= eng.ticks
        assert snap["counters"]["serving.preemptions_total"][""] >= 1
        # drained engine: gauge back to zero pages in use
        assert snap["gauges"]["serving.kv_pages_in_use"][""] == 0.0

    def test_disarmed_by_default_no_observable_state(self):
        from paddle_tpu.observability import metrics

        def ttft_count():
            cell = metrics.snapshot()["histograms"][
                "serving.ttft_seconds"].get("")
            return cell["count"] if cell else 0

        model = _tiny_model()
        before = ttft_count()
        eng = ContinuousBatchingEngine(model, max_batch=1, max_seq=64)
        eng.add_request(GenerationRequest([4, 9], max_new_tokens=3))
        _drain(eng)
        assert ttft_count() == before     # disarmed: no new observations
