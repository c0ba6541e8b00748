"""Goodput observatory (ISSUE 11): ledger bucket accounting (buckets sum
to measured wall), MFU gauge vs a hand-computed FLOPs/peak product on a
fixed toy model, the disarmed-overhead guard, per-execution device
telemetry (compile/execute histograms + per-execution collective counts
keyed by the trace-time executable tag), per-device memory gauges, and
the flight-recorder merge CLI."""
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import (device_events, goodput, metrics,
                                      spans, view)


def _reset():
    obs.enable(False)
    metrics.reset()
    spans.clear()
    goodput.reset()


@pytest.fixture(autouse=True)
def _clean():
    # before as well: under `--dist loadfile` the file that ran before this
    # one on the worker may have left a step in the ledger (the whole run
    # of PR 44 read 4 steps where 3 in the file's first test)
    _reset()
    yield
    _reset()


def _toy_step(n_steps=3, arm=True):
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    paddle.seed(0)
    net = nn.Linear(8, 4)
    o = opt.SGD(learning_rate=0.01, parameters=net.parameters())
    step = paddle.jit.TrainStep(net, o,
                                lambda x, y: F.mse_loss(net(x), y))
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    y = paddle.to_tensor(np.ones((4, 4), np.float32))
    if arm:
        obs.enable(True)
        goodput.open_window()
    for _ in range(n_steps):
        loss = step(x, y)
    return step, float(loss.numpy())


class TestLedger:
    def test_buckets_sum_to_wall(self):
        """Every closed window satisfies productive + badput == wall by
        construction, and the cumulative ledger covers the measured loop
        wall within tolerance."""
        obs.enable(True)
        goodput.open_window()
        t_loop0 = time.perf_counter()
        for _ in range(3):
            time.sleep(0.02)
            goodput.attribute("data_wait", 0.005)
            bd = goodput.step_boundary()
            assert bd is not None
            total = bd["productive"] + sum(bd["badput"].values())
            assert abs(total - bd["wall"]) < 1e-9
            assert bd["badput"]["data_wait"] == pytest.approx(0.005)
        loop_wall = time.perf_counter() - t_loop0
        s = goodput.summary()
        assert s["steps"] == 3
        assert s["wall_seconds"] == pytest.approx(loop_wall, rel=0.25)
        snap = metrics.snapshot()
        prod = snap["counters"]["goodput.productive_seconds_total"]
        bad = snap["counters"]["goodput.badput_seconds_total"]
        assert prod["category=device_execute"] > 0
        assert bad["category=data_wait"] == pytest.approx(0.015)
        assert snap["counters"]["goodput.steps_total"][""] == 3

    def test_trainstep_feeds_ledger(self):
        _toy_step(3)
        s = goodput.summary()
        assert s["steps"] == 3
        assert s["wall_seconds"] > 0
        snap = metrics.snapshot()
        # the first step's compile landed in a window as badput
        assert "category=compile" in \
            snap["counters"]["goodput.badput_seconds_total"]
        assert snap["gauges"]["goodput.step_flops"][""] > 0
        assert snap["gauges"]["goodput.last_step_seconds"][""] > 0

    def test_mfu_gauge_matches_hand_computed(self, monkeypatch):
        """MFU = executable cost_analysis FLOPs / (step wall * peak):
        with a pinned peak the gauge must equal the hand product."""
        monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1e9")
        _toy_step(3)
        snap = metrics.snapshot()["gauges"]
        flops = snap["goodput.step_flops"][""]
        wall = snap["goodput.last_step_seconds"][""]
        assert flops > 0 and wall > 0
        expected = flops / (wall * 1e9)
        assert snap["goodput.mfu"][""] == pytest.approx(expected)

    def test_fit_decomposes_data_wait_and_host_pull(self, tmp_path):
        """Model.fit: the loader's next() time lands in data_wait and
        the deferred loss syncs in host_pull."""
        import paddle_tpu.nn as nn
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as opt
        from paddle_tpu.io import Dataset

        class SlowDS(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                time.sleep(0.01)
                return (np.ones(4, np.float32),
                        np.ones(2, np.float32))

        net = nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(opt.SGD(learning_rate=0.01,
                              parameters=net.parameters()), F.mse_loss)
        obs.enable(True)
        model.fit(SlowDS(), batch_size=4, epochs=1, verbose=0, log_freq=1)
        snap = metrics.snapshot()
        bad = snap["counters"]["goodput.badput_seconds_total"]
        assert bad.get("category=data_wait", 0) > 0
        assert bad.get("category=host_pull", 0) > 0

    def test_disarmed_overhead(self):
        """Disarmed attribute/boundary are a single bool check: 200k
        calls in < 1s (same bound as the registry's own guard)."""
        assert not metrics.enabled()
        t0 = time.perf_counter()
        for _ in range(100_000):
            goodput.attribute("data_wait", 0.001)
            goodput.step_boundary()
        assert time.perf_counter() - t0 < 1.0
        assert goodput.summary()["steps"] == 0
        snap = metrics.snapshot()["counters"]
        assert snap["goodput.badput_seconds_total"] == {}

    def test_consumer_wait_dedups_under_timed_iter(self):
        """The prefetcher seam must not double-count a wait the fit
        loop's timed_iter is already timing."""
        obs.enable(True)

        def gen():
            for i in range(2):
                goodput.consumer_wait(5.0)   # inside next(): skipped
                yield i

        list(goodput.timed_iter(gen()))
        goodput.consumer_wait(0.5)           # outside: counted
        goodput.step_boundary()              # opens
        goodput.step_boundary()
        total = sum(goodput.summary()["badput_seconds"].values())
        assert total < 1.0                   # the 5.0s waits were deduped


class TestDeviceEvents:
    def test_per_execution_collective_counts(self):
        """Trace-time composition x execution count: a collective traced
        once into a tagged executable is counted on EVERY execution —
        the close of the trace-time-only caveat."""
        import jax
        import jax.numpy as jnp
        obs.enable(True)

        def f(x):
            device_events.note_traced_collective("all_reduce")
            return x + 1

        jf = jax.jit(f)
        for _ in range(3):
            with device_events.execution("testexec.toy"):
                jf(jnp.ones(3))
        snap = metrics.snapshot()
        execd = snap["counters"]["collective.executed_calls_total"]
        key = "executable=testexec.toy,op=all_reduce"
        assert execd[key] == 3
        exe = snap["histograms"]["xla.dispatch_seconds"]
        assert exe["executable=testexec.toy"]["count"] == 3

    def test_compile_durations_attributed_to_tag(self):
        _toy_step(2)
        snap = metrics.snapshot()
        comp = snap["histograms"].get("xla.compile_seconds", {})
        tagged = [k for k in comp if "executable=train_step" in k]
        assert tagged, comp.keys()
        exe = snap["histograms"]["xla.dispatch_seconds"]
        tag_cells = [k for k in exe if k.startswith("executable=train_step")]
        assert tag_cells and sum(exe[k]["count"] for k in tag_cells) == 2

    def test_retrace_replaces_composition(self):
        import jax
        import jax.numpy as jnp
        obs.enable(True)

        def f(x):
            device_events.note_traced_collective("all_gather")
            return x * 2

        jf = jax.jit(f)
        with device_events.execution("testexec.retrace"):
            jf(jnp.ones(3))
        with device_events.execution("testexec.retrace"):
            jf(jnp.ones(5))              # new shape: re-traces
        comp = device_events.tag_composition("testexec.retrace")
        assert comp == {"all_gather": 1}     # replaced, not doubled

    def test_disarmed_execution_records_nothing(self):
        assert not metrics.enabled()
        with device_events.execution("testexec.off"):
            pass
        assert metrics.snapshot()["histograms"].get(
            "xla.dispatch_seconds", {}) == {}


class TestDeviceMemoryGauges:
    def test_per_device_labeled_cells(self, monkeypatch):
        """Multi-chip hosts report each chip, not device 0 as the whole
        host: per-device labeled cells + the unlabeled host total."""
        import jax

        class FakeDev:
            def __init__(self, i, n):
                self.platform = "tpu"
                self.id = i
                self._n = n

            def memory_stats(self):
                return {"bytes_in_use": self._n,
                        "peak_bytes_in_use": self._n * 2}

        monkeypatch.setattr(jax, "local_devices",
                            lambda: [FakeDev(0, 100), FakeDev(1, 300)])
        obs.enable(True)
        mem = obs.update_device_memory_gauges()
        assert mem["bytes_in_use"] == 400
        assert mem["peak_bytes_in_use"] == 800
        assert mem["per_device"]["tpu:1"]["bytes_in_use"] == 300
        g = metrics.snapshot()["gauges"]
        assert g["device.bytes_in_use"][""] == 400
        assert g["device.bytes_in_use"]["device=tpu:0"] == 100
        assert g["device.bytes_in_use"]["device=tpu:1"] == 300
        assert g["device.peak_bytes_in_use"]["device=tpu:1"] == 600

    def test_device_cuda_helpers_honor_device_arg(self, monkeypatch):
        import jax

        import paddle_tpu.device as pdev

        class FakeDev:
            def __init__(self, i):
                self.platform = "tpu"
                self.id = i

            def memory_stats(self):
                return {"bytes_in_use": 10 * (self.id + 1),
                        "peak_bytes_in_use": 20 * (self.id + 1)}

        # LOCAL devices: on multi-host jobs the global list's entry i
        # may be another host's non-addressable chip
        monkeypatch.setattr(jax, "local_devices",
                            lambda: [FakeDev(0), FakeDev(1)])
        assert pdev.cuda.memory_allocated() == 10
        assert pdev.cuda.memory_allocated(1) == 20
        assert pdev.cuda.memory_allocated("tpu:1") == 20
        assert pdev.cuda.max_memory_allocated(1) == 40
        assert pdev.cuda.memory_allocated(7) == 0    # out of range: 0


class TestProfilerGoodput:
    def test_summary_payload_carries_goodput(self, tmp_path):
        from paddle_tpu.profiler import Profiler
        os.environ["PADDLE_TPU_PROFDIR"] = str(tmp_path / "prof")
        try:
            p = Profiler(timer_only=True)
            p.start()
            goodput.open_window()
            time.sleep(0.01)
            goodput.step_boundary()
            p.step()
            payload = p._summary_payload()
        finally:
            p.stop()
            os.environ.pop("PADDLE_TPU_PROFDIR")
        assert payload["goodput"]["steps"] == 1
        assert payload["goodput"]["wall_seconds"] > 0


# -- the flight-recorder merge CLI -------------------------------------------

class TestViewCLI:
    def _write(self, path, recs):
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")

    def test_merges_ranks_time_ordered_with_postmortem(self, tmp_path,
                                                       capsys):
        t = 1700000000.0
        self._write(tmp_path / "flight.rank0.inc0.jsonl", [
            {"ev": "flight_recorder_start", "ts": t, "pid": 1, "rank": "0"},
            {"ev": "span_begin", "sid": 1, "name": "elastic.train_step",
             "ts": t + 1.0},
            {"ev": "span_end", "sid": 1, "name": "elastic.train_step",
             "ts": t + 2.0, "dur_s": 1.0},
        ])
        self._write(tmp_path / "flight.rank1.inc0.jsonl", [
            {"ev": "flight_recorder_start", "ts": t + 0.5, "pid": 2,
             "rank": "1"},
            {"ev": "span_begin", "sid": 1, "name": "ckpt.save",
             "ts": t + 1.5},
            # no span_end: rank 1 died mid-save
        ])
        self._write(tmp_path / "flight.rank1.inc1.jsonl", [
            {"ev": "flight_recorder_start", "ts": t + 3.0, "pid": 3,
             "rank": "1", "incarnation": "1"},
        ])
        self._write(tmp_path / "supervisor_flight.jsonl", [
            {"ev": "spawn", "rank": 0, "incarnation": 0, "ts": t - 1},
            {"ev": "worker_death", "rank": 1, "rc": 137,
             "incarnation": 0, "generation": 1, "ts": t + 2.5},
            {"ev": "relaunch", "rank": 1, "incarnation": 1,
             "restart": 1, "ts": t + 2.6},
        ])
        rc = view.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        # time order across files: rank0 begin before rank1 begin before
        # the supervisor's death record
        i_r0 = out.index("elastic.train_step")
        i_r1 = out.index("ckpt.save")
        i_death = out.index("worker_death")
        assert i_r0 < i_r1 < i_death
        # origins tagged
        assert "r0.i0" in out and "r1.i0" in out and "r1.i1" in out
        assert "sup" in out
        # post-mortem names the span open at rank 1's death
        assert "OPEN at end: ckpt.save" in out
        assert "relaunch" in out

    def test_json_mode_and_missing_files(self, tmp_path, capsys):
        assert view.main([str(tmp_path / "nope")]) == 1
        self._write(tmp_path / "flight.rank0.inc0.jsonl", [
            {"ev": "dump", "reason": "atexit", "ts": 5.0,
             "open_spans": []},
        ])
        rc = view.main(["--json", str(tmp_path)])
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0
        rec = json.loads(out[0])
        assert rec["ev"] == "dump" and rec["_origin"] == "r0.i0"

    def test_skips_faulthandler_text(self, tmp_path, capsys):
        p = tmp_path / "flight.rank0.inc0.jsonl"
        with open(p, "w") as f:
            f.write(json.dumps({"ev": "span_begin", "sid": 1,
                                "name": "ckpt.save", "ts": 1.0}) + "\n")
            f.write("Fatal Python error: Segmentation fault\n")
            f.write('Thread 0x00007f (most recent call first):\n')
        rc = view.main([str(p)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "ckpt.save" in out
