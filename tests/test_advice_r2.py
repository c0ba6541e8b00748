"""Regression tests for round-2 advisor findings (ADVICE.md r2):

1. PS channel no longer uses a source-constant authkey, and the wire
   protocol only dispatches an explicit op allowlist.
2. The collective p2p accept loop survives a failed auth handshake
   (a port scan / wrong key must not kill the listener thread).
3. ONNX runtime Reduce* keepdims defaults to 1 per onnx.proto.
"""
import multiprocessing
import time

import numpy as np
import pytest


class TestPSAuth:
    def test_authkey_not_source_constant(self, monkeypatch):
        from paddle_tpu.distributed.ps import _auth
        monkeypatch.setenv("PADDLE_PS_AUTHKEY", "sekrit-per-job")
        assert _auth() == b"sekrit-per-job"
        monkeypatch.delenv("PADDLE_PS_AUTHKEY")
        monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS",
                           "10.0.0.1:8001,10.0.0.2:8001")
        derived = _auth()
        assert derived != b"paddle_tpu_ps" and len(derived) >= 16
        # different namespace (p2p channel) derives a DIFFERENT key from
        # the same job env — compromising one channel doesn't open both
        from paddle_tpu.distributed._auth import derive_authkey
        assert derive_authkey("PADDLE_P2P_AUTHKEY", "p2p") != derived

    def test_all_channels_use_derived_keys(self, monkeypatch):
        """rpc and elastic must not ship constant keys either (the r2
        finding covered PS; the review extended it to every channel)."""
        monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS", "10.0.0.1:8001")
        import paddle_tpu.distributed.elastic as elastic
        import paddle_tpu.distributed.rpc as rpc
        keys = {rpc._AUTH(),
                elastic.MembershipManager.__dict__["_AUTH"].fget(
                    object.__new__(elastic.MembershipManager))}
        assert b"paddle_tpu_rpc" not in keys
        assert b"paddle_tpu_elastic" not in keys
        assert len(keys) == 2  # namespace-separated

    def test_bare_local_key_files_are_per_namespace(self, monkeypatch,
                                                    tmp_path):
        """With no job env at all, each namespace gets its OWN 0600 key
        file — one leaked channel key must not open the others."""
        from paddle_tpu.distributed._auth import derive_authkey
        for var in ("PADDLE_MASTER", "PADDLE_TRAINER_ENDPOINTS",
                    "PADDLE_PSERVERS_IP_PORT_LIST", "PADDLE_PS_AUTHKEY",
                    "PADDLE_P2P_AUTHKEY", "PADDLE_JOB_AUTHKEY"):
            # PADDLE_JOB_AUTHKEY too: an in-process launcher run earlier
            # in the same worker leaves it in os.environ (launch/main.py)
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("HOME", str(tmp_path))
        k1 = derive_authkey("PADDLE_P2P_AUTHKEY", "p2p")
        k2 = derive_authkey("PADDLE_PS_AUTHKEY", "ps")
        assert k1 != k2
        assert (tmp_path / ".paddle_tpu_p2p_key").exists()
        assert (tmp_path / ".paddle_tpu_ps_key").exists()
        # stable on re-read
        assert derive_authkey("PADDLE_P2P_AUTHKEY", "p2p") == k1

    def test_derivation_uses_single_highest_priority_var(self, monkeypatch):
        """Derivation digests ONE var (first set wins), never a
        concatenation — a process seeing a SUBSET of the job vars must
        still derive the same key as one seeing all of them, as long as
        the highest-priority var is published everywhere."""
        from paddle_tpu.distributed._auth import derive_authkey
        monkeypatch.delenv("PADDLE_PS_AUTHKEY", raising=False)
        monkeypatch.setenv("PADDLE_MASTER", "10.0.0.1:9000")
        monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS", "10.0.0.1:8001")
        both = derive_authkey("PADDLE_PS_AUTHKEY", "ps")
        monkeypatch.delenv("PADDLE_TRAINER_ENDPOINTS")
        assert derive_authkey("PADDLE_PS_AUTHKEY", "ps") == both

    def test_service_rejects_unknown_ops(self, monkeypatch):
        from paddle_tpu.distributed.ps import ParameterServer, PSClient
        monkeypatch.setenv("PADDLE_PS_AUTHKEY", "test-key")
        ps = ParameterServer()
        ps.create_dense_table("w", (4,), "sgd")
        ps.serve("127.0.0.1:29551")
        try:
            cl = PSClient(endpoint="127.0.0.1:29551")
            # allowlisted op works
            assert cl.pull_dense("w").shape == (4,)
            # arbitrary method names are refused at the protocol layer
            with pytest.raises(RuntimeError, match="unknown PS op"):
                cl._call("shutdown")
            with pytest.raises(RuntimeError, match="unknown PS op"):
                cl._call("create_dense_table", "x", (1,))
            cl.close()
        finally:
            ps.shutdown()

    def test_server_survives_bad_authkey_client(self, monkeypatch):
        from multiprocessing.connection import Client

        from paddle_tpu.distributed.ps import ParameterServer, PSClient
        monkeypatch.setenv("PADDLE_PS_AUTHKEY", "right-key")
        ps = ParameterServer()
        ps.create_dense_table("w", (3,),
                              initializer=lambda s: np.ones(s, np.float32))
        ps.serve("127.0.0.1:29552")
        try:
            # attacker with the wrong key: handshake fails client-side
            with pytest.raises(Exception):
                c = Client(("127.0.0.1", 29552), authkey=b"wrong-key")
                c.recv()
            time.sleep(0.2)
            # the accept loop must still be alive for the honest client
            cl = PSClient(endpoint="127.0.0.1:29552", retries=20)
            np.testing.assert_allclose(cl.pull_dense("w"), np.ones(3))
            cl.close()
        finally:
            ps.shutdown()


class TestP2PAcceptLoop:
    def test_accept_loop_survives_handshake_failure(self, monkeypatch):
        """Crash the handshake with a raw connect-then-close ('port scan');
        the loop must keep accepting honest peers afterwards."""
        import socket

        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
        monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
        monkeypatch.setenv("PADDLE_P2P_AUTHKEY", "job-key")
        monkeypatch.setenv("PADDLE_P2P_BASE_PORT", "29660")
        import paddle_tpu.distributed.collective as C
        monkeypatch.setattr(C, "_p2p_listener", None)
        monkeypatch.setattr(C, "_p2p_inbox", None)
        C._ensure_p2p_server()
        try:
            for _ in range(3):  # scans that drop mid-handshake
                s = socket.create_connection(("127.0.0.1", 29660))
                s.close()
            time.sleep(0.3)
            # honest authenticated peer still gets through
            from multiprocessing.connection import Client
            conn = Client(("127.0.0.1", 29660), authkey=b"job-key")
            conn.send((1, np.arange(4)))
            conn.close()
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                q = C._p2p_inbox[1]
                if not q.empty():
                    # inbox entries are (payload, generation_tag) since
                    # ISSUE 13; an untagged legacy 2-tuple send lands
                    # with tag None
                    arr, tag = q.get()
                    np.testing.assert_array_equal(arr, np.arange(4))
                    assert tag is None
                    return
                time.sleep(0.05)
            pytest.fail("message from honest peer never arrived — "
                        "accept loop died on the handshake failure")
        finally:
            C._p2p_listener.close()
            monkeypatch.setattr(C, "_p2p_listener", None)


class TestOnnxKeepdimsDefault:
    def test_reduce_keepdims_defaults_to_one(self):
        """onnx.proto: keepdims attribute defaults to 1. Build a model
        record WITHOUT the attribute (as an external exporter might) and
        check the evaluator keeps the reduced dim."""
        from paddle_tpu.onnx.runtime import run_graph
        graph = {
            "inputs": [{"name": "x"}],
            "outputs": [{"name": "y"}],
            "initializers": {},
            "nodes": [{"op_type": "ReduceSum", "inputs": ["x"],
                       "outputs": ["y"], "attrs": {"axes": [1]}}],
        }
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        (y,) = run_graph(graph, {"x": x})
        assert y.shape == (2, 1)
        np.testing.assert_allclose(y, x.sum(1, keepdims=True))
