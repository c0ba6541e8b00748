"""Request-scope tracing (ISSUE 18): the attribution ledger's
sum(buckets)==wall-by-construction invariant, the registered event
vocabulary, the JSONL sink, the engine timeline, the gateway/router trace
id plumbing (X-Request-Trace in, X-Request-Id + SSE trace_id out), the
fleet-scope `GET /v1/trace/<id>` merge that survives a dead replica,
heat-oracle freshness (TTL expiry + evict-on-refresh + eject clears),
and the kill switch's zero-footprint guarantee. The end-to-end
subprocess drill (SIGKILL a real replica, trace served from its sink)
rides test_serving_fleet_chaos.py; the bench-scale parity and failover
scenarios ride benchmarks/serving_bench.py."""
import http.client
import json
import threading
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import (ContinuousBatchingEngine, EngineRunner,
                                  FleetRouter, GenerationRequest,
                                  ServingGateway)
from paddle_tpu.observability import metrics, reqtrace
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

TOL = 1e-6


@pytest.fixture(autouse=True)
def _clean():
    yield
    reqtrace.set_sink(None)
    reqtrace.clear()
    reqtrace.set_store_size(1024)
    obs.enable(False)
    metrics.reset()    # armed tests must not leak counts downstream


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=128, use_recompute=False)
    return LlamaForCausalLM(cfg)


def _drain(eng):
    while eng.has_work:
        eng.step()


# ---------------- the ledger -------------------------------------------------

class TestLedger:
    def test_sum_equals_wall_by_construction(self):
        tr = reqtrace.RequestTrace("t0", now=100.0)
        tr.charge("queue_wait", now=100.5)
        tr.charge("prefill_compute", now=101.25)
        for i in range(7):
            tr.charge("decode_compute", now=101.25 + 0.125 * (i + 1))
        tr.charge("stream_write", now=102.25)
        rec = tr.finish("served", "finished", now=102.25, n_tokens=7)
        assert rec["wall"] == pytest.approx(2.25, abs=TOL)
        assert sum(rec["buckets"].values()) == pytest.approx(
            rec["wall"], abs=TOL)
        assert rec["buckets"]["decode_compute"] == pytest.approx(
            0.875, abs=TOL)

    def test_preload_credits_bucket_and_wall(self):
        tr = reqtrace.RequestTrace("t1", now=10.0)
        tr.preload("failover", 0.75)
        tr.charge("queue_wait", now=10.5)
        rec = tr.finish("served", "finished", now=10.5)
        assert rec["wall"] == pytest.approx(1.25, abs=TOL)
        assert rec["buckets"]["failover"] == pytest.approx(0.75, abs=TOL)
        assert sum(rec["buckets"].values()) == pytest.approx(
            rec["wall"], abs=TOL)

    def test_unregistered_names_raise(self):
        tr = reqtrace.RequestTrace("t2")
        with pytest.raises(ValueError):
            tr.charge("gpu_time")
        with pytest.raises(ValueError):
            tr.event("prefil_chunk")
        with pytest.raises(ValueError):
            tr.finish("served", "arrival")   # non-terminal event

    def test_decode_ticks_coalesce(self):
        tr = reqtrace.RequestTrace("t3")
        for _ in range(50):
            tr.event("decode_tick")
        snap = tr.snapshot()
        assert snap["decode_ticks"] == 50
        assert snap["events"] == []          # counted, never stored

    def test_finish_idempotent(self):
        tr = reqtrace.RequestTrace("t4", now=1.0)
        tr.charge("queue_wait", now=2.0)
        first = tr.finish("shed", "shed", now=2.0)
        again = tr.finish("served", "finished", now=99.0)
        assert again["status"] == "shed"
        assert again["wall"] == first["wall"]

    def test_store_is_bounded_lru(self):
        reqtrace.clear()
        reqtrace.set_store_size(4)
        ids = [reqtrace.new_trace().trace_id for _ in range(6)]
        assert reqtrace.lookup(ids[0]) is None       # evicted
        assert reqtrace.lookup(ids[-1]) is not None
        assert len(reqtrace.traces()) == 4

    def test_parse_trace_header(self):
        tid = "a" * 32
        assert reqtrace.parse_trace_header(
            f"00-{tid}-00f067aa0ba902b7-01") == tid
        assert reqtrace.parse_trace_header("DEADBEEF") == "deadbeef"
        assert reqtrace.parse_trace_header("not hex!") is None
        assert reqtrace.parse_trace_header("ab") is None     # too short
        assert reqtrace.parse_trace_header(None) is None

    def test_sink_jsonl_roundtrip(self, tmp_path):
        path = str(tmp_path / "trace.rank0.inc0.jsonl")
        reqtrace.set_sink(path)
        tr = reqtrace.new_trace("feedc0de" * 4, now=5.0)
        tr.event("arrival", prompt_tokens=3)
        tr.charge("queue_wait", now=5.5)
        tr.finish("served", "finished", now=5.5, n_tokens=2)
        reqtrace.set_sink(None)
        recs = [json.loads(ln) for ln in open(path)]
        assert [r["ev"] for r in recs] == ["arrival", "finished",
                                           "terminal"]
        term = recs[-1]
        assert term["status"] == "served"
        assert sum(term["buckets"].values()) == pytest.approx(
            term["wall"], abs=TOL)


# ---------------- the engine timeline ---------------------------------------

class TestEngineTraces:
    def test_timeline_and_exact_ledger(self, model):
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       prefill_buckets=(8, 16, 32),
                                       max_chunk_tokens=8, ragged=True)
        req = GenerationRequest([3, 5, 7, 11, 13], max_new_tokens=6)
        eng.add_request(req)
        _drain(eng)
        tr = req.trace
        assert tr is not None and req.trace_id == tr.trace_id
        rec = tr.snapshot()
        assert rec["status"] == "served"
        assert sum(rec["buckets"].values()) == pytest.approx(
            rec["wall"], abs=TOL)
        names = [e["ev"] for e in rec["events"]]
        for must in ("arrival", "admitted", "prefill_chunk",
                     "first_token", "finished"):
            assert must in names, names
        # 6 tokens: the first from the prefill chunk, five from decode
        # ticks — fewer ticks where the self-speculative drafter had a
        # token accepted (this model repeats itself, so it does)
        accepted = sum(e["n"] for e in rec["events"]
                       if e["ev"] == "draft_accepted")
        assert rec["decode_ticks"] + accepted >= 5
        assert rec["buckets"]["prefill_compute"] > 0
        assert rec["buckets"]["decode_compute"] > 0

    def test_failover_preload_lands_in_ledger(self, model):
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       prefill_buckets=(8, 16),
                                       max_chunk_tokens=8, ragged=True)
        req = GenerationRequest([3, 5, 7], max_new_tokens=3)
        req.trace_id = "ab" * 16
        req.failover_preload_s = 0.5
        eng.add_request(req)
        _drain(eng)
        rec = req.trace.snapshot()
        assert rec["buckets"]["failover"] >= 0.5
        assert sum(rec["buckets"].values()) == pytest.approx(
            rec["wall"], abs=TOL)

    def test_kill_switch_leaves_zero_footprint(self, model):
        """FLAGS_request_trace=0: no trace objects, no store entries, no
        attribution/exemplar metric rows — tracing must be invisible,
        not merely cheap (the bench guards the scheduling parity)."""
        obs.enable(True)
        metrics.reset()
        reqtrace.clear()
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       prefill_buckets=(8, 16),
                                       max_chunk_tokens=8, ragged=True,
                                       request_trace=False)
        req = GenerationRequest([3, 5, 7], max_new_tokens=4)
        eng.add_request(req)
        _drain(eng)
        assert req.trace is None
        assert reqtrace.traces() == []
        snap = metrics.snapshot()
        assert not snap["histograms"].get("serving.attribution_seconds")
        for cells in snap["histograms"].values():
            for cell in cells.values():
                assert "exemplars" not in cell

    def test_armed_attribution_histogram_and_exemplars(self, model):
        obs.enable(True)
        metrics.reset()
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       prefill_buckets=(8, 16),
                                       max_chunk_tokens=8, ragged=True)
        req = GenerationRequest([3, 5, 7], max_new_tokens=4)
        eng.add_request(req)
        _drain(eng)
        snap = metrics.snapshot()
        attr = snap["histograms"]["serving.attribution_seconds"]
        buckets_seen = set()
        for key, cell in attr.items():
            assert cell["exemplars"], key
            for ex in cell["exemplars"].values():
                assert ex["trace_id"] == req.trace_id
            buckets_seen.add(key)
        assert any("prefill_compute" in k for k in buckets_seen)
        ttft = snap["histograms"]["serving.ttft_seconds"]
        assert any(cell.get("exemplars") for cell in ttft.values())


# ---------------- gateway surface -------------------------------------------

def _gw_post(port, body, headers=None, timeout=60):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("POST", "/v1/generate", body=json.dumps(body),
              headers=headers or {})
    return c, c.getresponse()


def _sse_terminal(raw):
    terminal = None
    for block in raw.split("\n\n"):
        block = block.strip()
        if block.startswith("event: "):
            name, _, data = block.partition("\n")
            terminal = (name[len("event: "):],
                        json.loads(data[len("data: "):]))
    return terminal


class TestGatewaySurface:
    def _gateway(self, model):
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       prefill_buckets=(8, 16),
                                       max_chunk_tokens=8, ragged=True)
        g = ServingGateway(runner=EngineRunner(eng), port=0,
                           keepalive_s=2.0)
        return g, g.start()

    def test_incoming_traceparent_honored_end_to_end(self, model):
        g, port = self._gateway(model)
        tid = "c0ffee00" * 4
        try:
            c, r = _gw_post(
                port, {"prompt": [3, 5, 7], "max_new_tokens": 3},
                headers={"X-Request-Trace":
                         f"00-{tid}-00f067aa0ba902b7-01"})
            assert r.status == 200
            assert r.getheader("X-Request-Id") == tid
            terminal = _sse_terminal(r.read().decode())
            c.close()
            assert terminal[0] == "end"
            assert terminal[1]["trace_id"] == tid
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            c.request("GET", f"/v1/trace/{tid}")
            tr = c.getresponse()
            assert tr.status == 200
            doc = json.loads(tr.read())
            c.close()
            assert doc["terminal"] and doc["status"] == "served"
            assert sum(doc["buckets"].values()) == pytest.approx(
                doc["wall"], abs=TOL)
            assert any(e["ev"] == "first_token" for e in doc["events"])
        finally:
            g.stop()

    def test_trace_minted_when_absent_and_unknown_404(self, model):
        g, port = self._gateway(model)
        try:
            c, r = _gw_post(port, {"prompt": [2, 4], "max_new_tokens": 2})
            tid = r.getheader("X-Request-Id")
            r.read()
            c.close()
            assert tid and len(tid) == 32 \
                and all(ch in "0123456789abcdef" for ch in tid)
            assert reqtrace.lookup(tid) is not None
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            c.request("GET", "/v1/trace/" + "0" * 32)
            assert c.getresponse().status == 404
            c.close()
        finally:
            g.stop()


# ---------------- router: heat freshness + fleet trace view ------------------

# the fake-replica fixture set from test_serving_fleet
from tests.test_serving_fleet import (_HEAD, _PROMPT,  # noqa: E402
                                      _FakeReplica, _router)


class TestHeatFreshness:
    def test_stale_heat_falls_back_to_least_loaded(self):
        cold, hot = _FakeReplica(), _FakeReplica(heat={_HEAD: 3})
        r = _router([cold, hot])
        try:
            c, resp = _gw_post(r.port, {"prompt": _PROMPT,
                                        "max_new_tokens": 2})
            resp.read(), c.close()
            assert len(hot.requests) == 1     # fresh heat: affinity wins
            # age the heat past the TTL without a refreshing probe: the
            # oracle no longer predicts the cache — route by load
            r.replicas[1].heat_mono -= r.heat_ttl_s + 1.0
            c, resp = _gw_post(r.port, {"prompt": _PROMPT,
                                        "max_new_tokens": 2})
            resp.read(), c.close()
            assert len(cold.requests) == 1 and len(hot.requests) == 1
        finally:
            r.stop(), cold.stop(), hot.stop()

    def test_eviction_on_refresh_routes_by_load(self):
        """The satellite regression: pages evicted on replica B must
        stop attracting B's old tenants after the next probe refresh."""
        cold, hot = _FakeReplica(), _FakeReplica(heat={_HEAD: 3})
        r = _router([cold, hot])
        try:
            c, resp = _gw_post(r.port, {"prompt": _PROMPT,
                                        "max_new_tokens": 2})
            resp.read(), c.close()
            assert len(hot.requests) == 1
            hot.cfg["heat"] = {}              # the engine evicted the pages
            r.probe_all()                     # refresh sees the empty map
            c, resp = _gw_post(r.port, {"prompt": _PROMPT,
                                        "max_new_tokens": 2})
            resp.read(), c.close()
            assert len(cold.requests) == 1 and len(hot.requests) == 1
        finally:
            r.stop(), cold.stop(), hot.stop()

    def test_eject_clears_heat(self):
        hot = _FakeReplica(heat={_HEAD: 3})
        r = _router([hot])
        try:
            rep = r.replicas[0]
            assert rep.heat and rep.heat_epoch is not None
            with r.lock:
                r._eject(rep, "test")
            assert rep.heat == {} and rep.heat_epoch == -1
        finally:
            r.stop(), hot.stop()


class TestFleetTraceView:
    def test_merges_dead_replicas_sink(self, tmp_path):
        """The SIGKILL contract in miniature: a replica's sink JSONL is
        all that remains of it, and the router's fleet-scope
        /v1/trace/<id> still reconstructs the timeline from it."""
        tid = "dead00" + "ab" * 13
        sink = tmp_path / "trace.rank1.inc2.jsonl"
        with open(sink, "w") as f:
            for rec in (
                {"trace_id": tid, "ev": "arrival", "ts": 10.0,
                 "prompt_tokens": 5},
                {"trace_id": tid, "ev": "first_token", "ts": 10.4,
                 "ttft_s": 0.4},
                {"trace_id": tid, "ev": "finished", "ts": 10.6,
                 "n_tokens": 3},
                {"trace_id": tid, "ev": "terminal", "ts": 10.6,
                 "status": "served", "wall": 0.6,
                 "buckets": {"queue_wait": 0.1, "prefill_compute": 0.3,
                             "decode_compute": 0.2},
                 "decode_ticks": 3, "events": []},
                {"trace_id": "f" * 32, "ev": "arrival", "ts": 11.0},
            ):
                f.write(json.dumps(rec) + "\n")
        fake = _FakeReplica()
        r = _router([fake], snapshot_dir=str(tmp_path))
        try:
            c = http.client.HTTPConnection("127.0.0.1", r.port, timeout=10)
            c.request("GET", f"/v1/trace/{tid}")
            resp = c.getresponse()
            assert resp.status == 200
            doc = json.loads(resp.read())
            c.close()
            assert doc["terminal"] and doc["status"] == "served"
            assert sum(doc["buckets"].values()) == pytest.approx(
                doc["wall"], abs=TOL)
            assert [e["ev"] for e in doc["events"]] == [
                "arrival", "first_token", "finished"]
            # every merged event names its source replica+incarnation
            assert all(e["replica"] == 1 and e["incarnation"] == 2
                       for e in doc["events"])
            c = http.client.HTTPConnection("127.0.0.1", r.port, timeout=10)
            c.request("GET", "/v1/trace/" + "0" * 32)
            assert c.getresponse().status == 404
            c.close()
        finally:
            r.stop(), fake.stop()

    def test_midstream_death_names_the_hop(self, tmp_path):
        """A replica dying mid-stream: the client's error frame carries
        the trace id, the fleet recorder logs a failover_hop with the
        same id, and the router's trace view serves the hop."""
        hops = []
        dying = _FakeReplica(heat={_HEAD: 3}, mode="die_midstream",
                             die_after_frames=1)
        r = _router([dying], snapshot_dir=str(tmp_path),
                    recorder=hops.append)
        tid = "ba5eba11" * 4
        try:
            c, resp = _gw_post(
                r.port, {"prompt": _PROMPT, "max_new_tokens": 6},
                headers={"X-Request-Trace": tid})
            assert resp.getheader("X-Request-Id") == tid
            terminal = _sse_terminal(resp.read().decode())
            c.close()
            assert terminal[0] == "error"
            assert terminal[1]["trace_id"] == tid
            hop_recs = [h for h in hops if h.get("ev") == "failover_hop"]
            assert hop_recs and hop_recs[0]["trace_id"] == tid
            c = http.client.HTTPConnection("127.0.0.1", r.port, timeout=10)
            c.request("GET", f"/v1/trace/{tid}")
            resp = c.getresponse()
            assert resp.status == 200
            doc = json.loads(resp.read())
            c.close()
            assert doc["hops"] and doc["hops"][0]["replica"] == 0
            assert "died mid-stream" in doc["hops"][0]["reason"]
        finally:
            r.stop(), dying.stop()
