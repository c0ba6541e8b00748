"""Advisor round-3 findings (ADVICE.md r3): exposed-listener authkey
guard, launcher job secret, auth-mismatch hints, autotune cache
cross-process merge, Config warn-once."""
import json
import os

import numpy as np
import pytest

from paddle_tpu.distributed._auth import authkey_source, derive_authkey

_ALL_AUTH_VARS = ("PADDLE_MASTER", "PADDLE_TRAINER_ENDPOINTS",
                  "PADDLE_PSERVERS_IP_PORT_LIST", "PADDLE_JOB_AUTHKEY",
                  "PADDLE_PS_AUTHKEY", "PADDLE_P2P_AUTHKEY",
                  "PADDLE_ALLOW_DERIVED_AUTHKEY")


@pytest.fixture
def clean_env(monkeypatch):
    for var in _ALL_AUTH_VARS:
        monkeypatch.delenv(var, raising=False)
    return monkeypatch


class TestExposedListenerGuard:
    def test_loopback_bind_keeps_derived_fallback(self, clean_env):
        clean_env.setenv("PADDLE_MASTER", "10.0.0.1:9000")
        k = derive_authkey("PADDLE_PS_AUTHKEY", "ps",
                           bind_host="127.0.0.1")
        assert isinstance(k, bytes) and len(k) == 32

    def test_nonloopback_bind_refuses_derived_key(self, clean_env):
        clean_env.setenv("PADDLE_MASTER", "10.0.0.1:9000")
        with pytest.raises(RuntimeError, match="refusing to bind"):
            derive_authkey("PADDLE_PS_AUTHKEY", "ps",
                           bind_host="10.0.0.2")

    def test_nonloopback_bind_refuses_keyfile(self, clean_env):
        with pytest.raises(RuntimeError, match="refusing to bind"):
            derive_authkey("PADDLE_P2P_AUTHKEY", "p2p",
                           bind_host="0.0.0.0")

    def test_explicit_secret_allows_nonloopback(self, clean_env):
        clean_env.setenv("PADDLE_PS_AUTHKEY", "per-job-secret")
        k = derive_authkey("PADDLE_PS_AUTHKEY", "ps", bind_host="0.0.0.0")
        assert k == b"per-job-secret"

    def test_job_authkey_allows_nonloopback_and_namespaces(self, clean_env):
        clean_env.setenv("PADDLE_JOB_AUTHKEY", "a" * 64)
        k1 = derive_authkey("PADDLE_PS_AUTHKEY", "ps", bind_host="0.0.0.0")
        k2 = derive_authkey("PADDLE_P2P_AUTHKEY", "p2p",
                            bind_host="0.0.0.0")
        assert k1 != k2                       # per-channel isolation
        assert k1 == derive_authkey("PADDLE_PS_AUTHKEY", "ps")

    def test_override_env_downgrades_to_warning(self, clean_env):
        clean_env.setenv("PADDLE_MASTER", "10.0.0.1:9000")
        clean_env.setenv("PADDLE_ALLOW_DERIVED_AUTHKEY", "1")
        with pytest.warns(RuntimeWarning, match="network-adjacent"):
            k = derive_authkey("PADDLE_PS_AUTHKEY", "ps",
                               bind_host="10.9.9.9")
        assert len(k) == 32

    def test_client_side_derivation_unaffected(self, clean_env):
        clean_env.setenv("PADDLE_MASTER", "10.0.0.1:9000")
        # no bind_host (a connecting client) — derived key stays fine
        assert len(derive_authkey("PADDLE_PS_AUTHKEY", "ps")) == 32


class TestAuthkeySourceHint:
    def test_source_strings(self, clean_env):
        assert "key file" in authkey_source("PADDLE_PS_AUTHKEY")
        clean_env.setenv("PADDLE_TRAINER_ENDPOINTS", "127.0.0.1:1")
        s = authkey_source("PADDLE_PS_AUTHKEY")
        assert "PADDLE_TRAINER_ENDPOINTS" in s and "subset" in s
        clean_env.setenv("PADDLE_JOB_AUTHKEY", "x")
        assert "PADDLE_JOB_AUTHKEY" in authkey_source("PADDLE_PS_AUTHKEY")
        clean_env.setenv("PADDLE_PS_AUTHKEY", "y")
        assert "explicit" in authkey_source("PADDLE_PS_AUTHKEY")


class TestLauncherJobSecret:
    def test_single_node_env_gets_random_job_key(self, monkeypatch):
        from paddle_tpu.distributed.launch.main import (_bootstrap_env,
                                                        _parse)
        monkeypatch.delenv("PADDLE_JOB_AUTHKEY", raising=False)
        args = _parse(["train.py"])
        env = _bootstrap_env(args)
        assert len(env["PADDLE_JOB_AUTHKEY"]) == 64
        # distinct per job
        assert (_bootstrap_env(args)["PADDLE_JOB_AUTHKEY"]
                != env["PADDLE_JOB_AUTHKEY"])

    def test_multi_node_does_not_invent_divergent_keys(self, monkeypatch):
        from paddle_tpu.distributed.launch.main import (_bootstrap_env,
                                                        _parse)
        monkeypatch.delenv("PADDLE_JOB_AUTHKEY", raising=False)
        args = _parse(["--nnodes", "2", "--rank", "0", "train.py"])
        env = _bootstrap_env(args)
        assert "PADDLE_JOB_AUTHKEY" not in env

    def test_operator_key_passes_through(self, monkeypatch):
        from paddle_tpu.distributed.launch.main import (_bootstrap_env,
                                                        _parse)
        monkeypatch.setenv("PADDLE_JOB_AUTHKEY", "opkey")
        env = _bootstrap_env(_parse(["train.py"]))
        assert env["PADDLE_JOB_AUTHKEY"] == "opkey"


class TestAutotuneCacheMerge:
    def test_concurrent_writer_entries_survive(self, tmp_path, monkeypatch):
        """record() must MERGE with what is on disk, not clobber it with
        a stale in-memory snapshot (advisor r3: parallel sweeps)."""
        from paddle_tpu.kernels import autotune
        path = tmp_path / "cache.json"
        monkeypatch.setenv("PADDLE_AUTOTUNE_CACHE", str(path))
        monkeypatch.setattr(autotune, "_user_cache", None)
        monkeypatch.setattr(autotune, "_memo", {})
        autotune.record("k1", [256, 512])
        # another process writes k2 directly (this process's snapshot is
        # now stale)
        disk = json.loads(path.read_text())
        disk["k2"] = {"best": [128, 128]}
        path.write_text(json.dumps(disk))
        autotune.record("k3", [512, 512])
        final = json.loads(path.read_text())
        assert set(final) == {"k1", "k2", "k3"}, final
        autotune.forget("k1")
        final = json.loads(path.read_text())
        assert set(final) == {"k2", "k3"}, final


class TestListenerClosedEvent:
    def test_event_is_authoritative_and_per_listener(self):
        import threading

        from paddle_tpu.distributed import collective as C

        class _Boom:
            @property
            def _listener(self):
                raise RuntimeError("internals changed")

        mine = _Boom()
        mine._paddle_shutdown = threading.Event()
        # probe failure alone must NOT read as closed (would kill the
        # accept loop on any transient error)
        assert C._listener_closed(mine) is False
        mine._paddle_shutdown.set()
        assert C._listener_closed(mine) is True
        # a FOREIGN listener (PS/RPC reusing the helper) is untouched by
        # p2p teardown — no cross-service poisoning (code-review r4)
        other = _Boom()
        assert C._listener_closed(other) is False


class TestDestroyProcessGroupWiresShutdown:
    def test_destroy_sets_event_and_closes(self):
        import threading

        from paddle_tpu.distributed import collective as C

        class _FakeListener:
            closed = False

            def close(self):
                self.closed = True

        ev = threading.Event()
        lst = _FakeListener()
        old = (C._p2p_shutdown, C._p2p_listener, C._p2p_inbox)
        try:
            C._p2p_shutdown = ev
            C._p2p_listener = lst
            C._p2p_inbox = {}
            C.destroy_process_group()
            assert ev.is_set()             # accept loop sees closure
            assert lst.closed
            assert C._p2p_listener is None
        finally:
            C._p2p_shutdown, C._p2p_listener, C._p2p_inbox = old


class TestConfigWarnOnce:
    def test_ignored_toggle_warns_once(self):
        import warnings

        import paddle_tpu.inference as inf
        inf._warned_noops.discard("enable_tensorrt_engine")
        cfg = inf.Config("m")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            cfg.enable_tensorrt_engine(max_batch_size=4)
            cfg.enable_tensorrt_engine(max_batch_size=4)
        msgs = [x for x in w if "enable_tensorrt_engine" in str(x.message)]
        assert len(msgs) == 1


def test_kernel_route_kill_switches():
    """FLAGS_use_fused_ce gates the Pallas cross-entropy route (the
    on-chip ablation lever; ref: phi kill-switch flags)."""
    import paddle_tpu as paddle
    from paddle_tpu.kernels import cross_entropy as fck

    # defaults: gates defer to the backend check only (False on CPU,
    # but the flag consult must not throw and must honor an override).
    # Restore the PRIOR value, not a hardcoded one — the shipped
    # default changed once already (r5: fused CE off until proven).
    prior_ce = paddle.get_flags(["FLAGS_use_fused_ce"])[
        "FLAGS_use_fused_ce"]
    paddle.set_flags({"FLAGS_use_fused_ce": False})
    try:
        assert fck.supported(32000) is False
    finally:
        paddle.set_flags({"FLAGS_use_fused_ce": prior_ce})

    # env-string form (the bench/session ablation path) normalizes
    import os
    os.environ["FLAGS_use_fused_ce"] = "0"
    try:
        assert fck.supported(32000) is False
    finally:
        del os.environ["FLAGS_use_fused_ce"]
