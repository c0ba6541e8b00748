"""Streaming HTTP gateway over the engine (ISSUE 12): SSE token
streams, 429 + Retry-After backpressure, /healthz readiness, mid-stream
disconnect cancellation, graceful drain, the serving.http_request chaos
point, headless /v1/infer, and the `python -m paddle_tpu.inference.serve`
subprocess end-to-end (the tier-1 smoke the runbook names)."""
import json
import os
import socket
import tempfile
import time

import http.client

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import (ContinuousBatchingEngine, EngineRunner,
                                  GenerationRequest, ServingGateway,
                                  load_generation_model, save_for_serving)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.utils import fault_injection as fi


@pytest.fixture(autouse=True)
def _clean():
    yield
    fi.configure(None)
    obs.enable(False)


def _tiny_model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=128, use_recompute=False)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


@pytest.fixture(scope="module")
def served(model):
    """One live gateway shared by the read-mostly tests (each request
    leaves the engine drained)."""
    eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                   max_chunk_tokens=8,
                                   max_queue_tokens=64)
    runner = EngineRunner(eng)
    g = ServingGateway(runner=runner, port=0, keepalive_s=0.2)
    port = g.start()
    yield g, port, eng, runner
    g.stop()


def _post(port, body, timeout=120):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("POST", "/v1/generate", body=json.dumps(body))
    return c.getresponse()


def _get(port, path, timeout=30):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    c.request("GET", path)
    return c.getresponse()


def _sse_frames(raw: str):
    """Parse an SSE body into (token frames, terminal event). Each data
    frame carries ALL tokens its tick accepted (ISSUE 15: one write per
    request per tick — speculation makes multi-token ticks common)."""
    frames, terminal = [], None
    for block in raw.split("\n\n"):
        block = block.strip()
        if block.startswith("data: "):
            frames.append(json.loads(block[len("data: "):])["tokens"])
        elif block.startswith("event: "):
            name, _, data = block.partition("\n")
            terminal = (name[len("event: "):],
                        json.loads(data[len("data: "):]))
    return frames, terminal


def _sse_tokens(raw: str):
    frames, terminal = _sse_frames(raw)
    return [t for f in frames for t in f], terminal


def _reference_generate(model, prompt, n_new):
    out = model.generate(paddle.to_tensor(np.array([prompt], np.int32)),
                         max_new_tokens=n_new, do_sample=False)
    return [int(t) for t in np.asarray(out.numpy())[0][:n_new]]


def _wait_idle(runner, timeout=30):
    t0 = time.time()
    while time.time() - t0 < timeout:
        with runner.lock:
            if not runner.engine.has_work:
                return True
        time.sleep(0.05)
    return False


class TestWire:
    def test_stream_matches_reference(self, served, model):
        _, port, _, _ = served
        ref = _reference_generate(model, [3, 5, 7], 6)
        r = _post(port, {"prompt": [3, 5, 7], "max_new_tokens": 6})
        assert r.status == 200
        assert r.getheader("Content-Type") == "text/event-stream"
        toks, terminal = _sse_tokens(r.read().decode())
        assert toks == ref
        name, payload = terminal
        assert name == "end"
        # the end frame carries the request's trace id (ISSUE 18): the
        # client-visible handle for GET /v1/trace/<id>
        tid = payload.pop("trace_id")
        assert len(tid) == 32 and tid == r.getheader("X-Request-Id")
        assert payload == {"status": "served", "n_tokens": 6}

    def test_non_stream_document(self, served, model):
        _, port, _, _ = served
        ref = _reference_generate(model, [9, 4], 5)
        r = _post(port, {"prompt": [9, 4], "max_new_tokens": 5,
                         "stream": False})
        assert r.status == 200
        body = json.loads(r.read())
        assert len(body.pop("trace_id")) == 32
        assert body == {"status": "served", "output": ref}

    def test_bad_requests(self, served):
        _, port, _, runner = served
        assert _post(port, {"prompt": "not tokens"}).status == 400
        assert _post(port, {}).status == 400
        # oversized prompt rejected at submit -> 400, not a wedged queue
        assert _post(port, {"prompt": [1] * 500}).status == 400
        # garbage numeric fields answer 400 and NEVER reach the engine:
        # a non-numeric deadline_s would blow up _slo_pre_tick OUTSIDE
        # the tick isolation boundary and kill the whole loop
        assert _post(port, {"prompt": [1],
                            "deadline_s": "abc"}).status == 400
        assert _post(port, {"prompt": [1],
                            "max_new_tokens": "lots"}).status == 400
        assert _post(port, {"prompt": [1],
                            "max_new_tokens": 0}).status == 400
        assert _post(port, {"prompt": [1],
                            "priority": [2]}).status == 400
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        c.request("POST", "/v1/generate", body="{not json")
        assert c.getresponse().status == 400
        assert _get(port, "/nope").status == 404
        # ...and the loop is alive afterwards
        r = _post(port, {"prompt": [5, 6], "max_new_tokens": 2,
                         "stream": False})
        assert json.loads(r.read())["status"] == "served"
        assert runner.fatal is None

    def test_healthz_503_when_engine_queue_full(self, model):
        """/healthz readiness keys on the ENGINE's accepting too: a
        saturated queue reads 503 + Retry-After so the balancer stops
        routing here (not just draining/fatal)."""
        eng = ContinuousBatchingEngine(model, max_batch=1, max_seq=64,
                                       max_chunk_tokens=8,
                                       max_queue_tokens=8)
        runner = EngineRunner(eng)
        g = ServingGateway(runner=runner, port=0, keepalive_s=0.2)
        port = g.start()
        try:
            # park the tick thread so the queue state is deterministic
            runner._stop.set()
            runner._wake.set()
            runner._thread.join(timeout=10)
            runner.submit(GenerationRequest([1] * 8, max_new_tokens=4))
            r = _get(port, "/healthz")
            assert r.status == 503
            assert r.getheader("Retry-After")
            body = json.loads(r.read())
            assert body["accepting"]                    # gateway gate open
            assert not body["engine"]["accepting"]      # engine gate shut
        finally:
            g.stop()

    def test_healthz_and_metrics(self, served):
        _, port, _, _ = served
        obs.enable(True)
        r = _get(port, "/healthz")
        assert r.status == 200
        body = json.loads(r.read())
        assert body["accepting"] and body["engine"]["ready"]
        assert "prefix_cache" in body["engine"]
        r = _get(port, "/metrics")
        text = r.read().decode()
        assert "gateway_requests_total" in text
        assert "serving_prefix_hits_total" in text

    def test_queue_full_429_with_finite_retry_after(self, model):
        eng = ContinuousBatchingEngine(model, max_batch=1, max_seq=64,
                                       max_chunk_tokens=8,
                                       max_queue_tokens=24)
        runner = EngineRunner(eng)
        g = ServingGateway(runner=runner, port=0, keepalive_s=0.2)
        port = g.start()
        try:
            # park the tick thread while the queue fills: speculative
            # decoding drains multi-token ticks too fast for a
            # sleep-raced setup to deterministically stay full
            runner._stop.set()
            runner._wake.set()
            runner._thread.join(timeout=10)
            conns = []
            for i in range(3):
                c = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=120)
                c.request("POST", "/v1/generate", body=json.dumps(
                    {"prompt": [3 + i, 5, 7, 9, 11, 2, 4, 6],
                     "max_new_tokens": 30}))
                conns.append(c)
            t0 = time.time()
            while len(eng.waiting) < 3 and time.time() - t0 < 30:
                time.sleep(0.01)         # handler threads registering
            assert len(eng.waiting) == 3     # 24 queued tokens = bound
            r = _post(port, {"prompt": [9] * 10, "max_new_tokens": 4})
            assert r.status == 429
            ra = r.getheader("Retry-After")
            assert ra is not None and 1 <= float(ra) < 1e6
            body = json.loads(r.read())
            assert 0 < body["retry_after_s"] < 1e6
            # resume ticking: every ACCEPTED request terminates with a
            # structured frame — served, or shed by the SLO layer under
            # this engineered starvation (nothing wedges or times out)
            runner.start()
            statuses = []
            for c in conns:
                _, terminal = _sse_tokens(c.getresponse().read().decode())
                assert terminal is not None
                statuses.append(terminal[1]["status"])
            assert "served" in statuses
            assert set(statuses) <= {"served", "shed"}, statuses
        finally:
            g.stop()

    def test_client_disconnect_cancels_and_frees(self, served, model):
        """Close the socket mid-stream: the request goes terminal
        `cancelled`, slot + pages are reclaimed, and the tick loop
        keeps serving."""
        _, port, eng, runner = served
        body = json.dumps({"prompt": [3, 5, 7],
                           "max_new_tokens": 500}).encode()
        s = socket.create_connection(("127.0.0.1", port), timeout=30)
        s.sendall(b"POST /v1/generate HTTP/1.0\r\n"
                  b"Content-Length: %d\r\n\r\n" % len(body) + body)
        buf = b""
        while b"data: " not in buf:       # stream is live
            buf += s.recv(4096)
        s.close()
        assert _wait_idle(runner, timeout=30), "engine wedged on a " \
            "dead client"
        with runner.lock:
            assert eng.pool.n_free == eng.pool.n_pages - 1
        # the tick loop still serves
        ref = _reference_generate(model, [5, 6], 3)
        r = _post(port, {"prompt": [5, 6], "max_new_tokens": 3,
                         "stream": False})
        assert json.loads(r.read())["output"] == ref

    def test_http_request_fault_mid_stream(self, served, model):
        """serving.http_request raise mid-stream: the client gets a
        structured error frame, the engine reclaims the request."""
        _, port, eng, runner = served
        # hit 1 = request admission, 2 = first tokens frame, 3 = second
        fi.configure("serving.http_request:raise@3")
        r = _post(port, {"prompt": [3, 5, 7], "max_new_tokens": 20})
        raw = r.read().decode()
        fi.configure(None)
        frames, terminal = _sse_frames(raw)
        # exactly one frame landed before the kill (it may carry several
        # tokens — one frame per tick, and a tick can accept many)
        assert len(frames) == 1 and len(frames[0]) >= 1
        toks = frames[0]
        assert len(toks) < 20
        assert terminal is not None and terminal[0] == "error"
        assert terminal[1]["status"] == "failed"
        assert "FaultInjected" in terminal[1]["error"]
        assert _wait_idle(runner, timeout=30)
        with runner.lock:
            assert eng.pool.n_free == eng.pool.n_pages - 1

    def test_drain_stops_accepting_and_finishes_inflight(self, model):
        eng = ContinuousBatchingEngine(model, max_batch=2, max_seq=64,
                                       max_chunk_tokens=8,
                                       max_queue_tokens=64)
        runner = EngineRunner(eng)
        g = ServingGateway(runner=runner, port=0, keepalive_s=0.2)
        port = g.start()
        try:
            c = http.client.HTTPConnection("127.0.0.1", port,
                                           timeout=120)
            c.request("POST", "/v1/generate", body=json.dumps(
                {"prompt": [3, 5, 7], "max_new_tokens": 20}))
            time.sleep(0.3)              # in-flight
            assert g.drain(timeout=60)
            r = _get(port, "/healthz")
            assert r.status == 503 and r.getheader("Retry-After")
            r2 = _post(port, {"prompt": [5], "max_new_tokens": 2})
            assert r2.status == 503
            # the in-flight stream finished cleanly during the drain
            raw = c.getresponse().read().decode()
            assert "event: end" in raw
        finally:
            g.stop()


class TestModelLoading:
    def test_save_load_roundtrip_and_presets(self, model, tmp_path):
        prefix = os.path.join(str(tmp_path), "m")
        save_for_serving(model, prefix)
        assert os.path.exists(prefix + ".pdparams")
        assert os.path.exists(prefix + ".config.json")
        m2 = load_generation_model(prefix)     # sidecar config
        assert m2.cfg.hidden_size == model.cfg.hidden_size
        ref = _reference_generate(model, [3, 5, 7], 4)
        assert _reference_generate(m2, [3, 5, 7], 4) == ref
        from paddle_tpu.inference import resolve_config
        assert resolve_config("llama_tiny").num_hidden_layers == 2
        with pytest.raises(ValueError):
            resolve_config("no_such_preset")
        with pytest.raises(FileNotFoundError):
            load_generation_model(os.path.join(str(tmp_path), "other"))

    def test_static_infer_endpoint(self, tmp_path):
        from paddle_tpu import nn
        paddle.enable_static()
        try:
            prog = paddle.static.Program()
            with paddle.static.program_guard(prog):
                x = paddle.static.data("x", [2, 8], "float32")
                paddle.seed(1)
                y = paddle.tanh(nn.Linear(8, 3)(x))
            exe = paddle.static.Executor()
            feed = np.random.default_rng(2).standard_normal(
                (2, 8)).astype(np.float32)
            want, = exe.run(prog, feed={"x": feed}, fetch_list=[y])
            path = os.path.join(str(tmp_path), "model")
            paddle.static.save_inference_model(path, [x], [y], exe,
                                               program=prog)
        finally:
            paddle.disable_static()
        from paddle_tpu.inference import load_static_model
        sm = load_static_model(path)
        assert sm.feed_names == ["x"]
        assert sm.fetch_vars and sm.fetch_vars[0].shape == (2, 3)
        g = ServingGateway(static_model=sm, port=0)
        port = g.start()
        try:
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            c.request("POST", "/v1/infer", body=json.dumps(
                {"feeds": {"x": feed.tolist()}}))
            r = c.getresponse()
            assert r.status == 200
            got = np.asarray(json.loads(r.read())["fetches"][0])
            np.testing.assert_allclose(got, want, rtol=1e-5)
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            c.request("POST", "/v1/infer", body=json.dumps({"feeds": {}}))
            assert c.getresponse().status == 400
            # generate on a static-only gateway is 501, not a crash
            r = _post(port, {"prompt": [1]})
            assert r.status == 501
        finally:
            g.stop()


@pytest.mark.timeout(300)
def test_serve_cli_end_to_end(model, tmp_path):
    """Acceptance: `python -m paddle_tpu.inference.serve` on a
    jit.save'd model streams tokens over HTTP; SIGTERM drains."""
    import re
    import signal
    import subprocess
    import sys
    prefix = os.path.join(str(tmp_path), "m")
    save_for_serving(model, prefix)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.inference.serve",
         "--model", prefix, "--port", "0", "--max-batch", "2",
         "--max-seq", "64", "--max-chunk-tokens", "8",
         "--max-queue-tokens", "64", "--keepalive-s", "0.2"],
        cwd=repo, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        # stderr is merged in: XLA may log (a compile-cache load, say)
        # before the startup line, as the fleet's supervisor allows for
        m, seen = None, []
        while m is None and (line := proc.stdout.readline()):
            seen.append(line)
            m = re.search(r"http://127\.0\.0\.1:(\d+)", line)
        assert m, f"no startup line: {seen!r}"
        port = int(m.group(1))
        ref = _reference_generate(model, [3, 5, 7], 5)
        r = _post(port, {"prompt": [3, 5, 7], "max_new_tokens": 5})
        toks, terminal = _sse_tokens(r.read().decode())
        assert toks == ref and terminal[0] == "end"
        assert _get(port, "/healthz").status == 200
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        rest = proc.stdout.read()
        assert rc == 0 and "drained, bye" in rest
    finally:
        if proc.poll() is None:
            proc.kill()
