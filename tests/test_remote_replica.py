"""Out-of-process replica tests (serve/remote.py + serve/replica_main.py).

The wire protocol and exception codec are tested in-process; the process
tests spawn the STUB backend (serve/replica_main.py's StubEngine — the full
warmup/submit/drain surface minus jax, deterministic rows per seed) so a
child boots in well under a second and the whole file fits the tier-1
budget. The chaos recipes: ``replica.kill`` is a
real SIGKILL inside the child, ``replica.hang`` wedges its reader thread
(heartbeat-loss retire), ``rpc.drop`` eats frames on the parent side.
"""

import socket
import struct
import subprocess
import threading
import time

import numpy as np
import pytest

from ddim_cold_tpu.serve import fleet, remote, replica_main
from ddim_cold_tpu.serve.batching import SamplerConfig
from ddim_cold_tpu.serve.errors import (DeadlineExceeded, EngineClosedError,
                                        RemoteRPCError, ReplicaCrashedError,
                                        ReplicaUnreachableError,
                                        RequestFailedError, decode_exception,
                                        encode_exception)
from ddim_cold_tpu.serve.router import Router
from ddim_cold_tpu.utils import faults

pytestmark = pytest.mark.usefixtures("no_leaked_faults")

CFG = SamplerConfig(k=50)
STUB_SHAPE = (8, 8, 3)


@pytest.fixture()
def no_leaked_faults():
    assert not faults.active(), "a previous test leaked an armed fault scope"
    yield
    assert not faults.active(), "this test leaked an armed fault scope"


@pytest.fixture()
def reaper():
    """Track spawned handles; guarantee no child process outlives a test
    (a hung child would otherwise linger for its full hang_s)."""
    handles = []
    yield handles
    for rep in handles:
        try:
            rep.close()
        except Exception:  # noqa: BLE001 — teardown is best-effort
            pass
        try:
            rep._proc.kill()
        except Exception:  # noqa: BLE001 — already gone is fine
            pass


def _spawn(reaper, spec=None, env=None, **kw):
    kw.setdefault("heartbeat_s", 0.3)
    kw.setdefault("miss_budget", 3)
    kw.setdefault("rpc_timeout_s", 10.0)
    factory = remote.remote_factory(
        dict({"backend": "stub"}, **(spec or {})), env=env, **kw)
    rep = factory("rk")
    reaper.append(rep)
    return rep


def _poll(fn, timeout=15.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(0.05)
    return False


# ------------------------------------------------------------ wire protocol


def test_payload_round_trip_with_arrays():
    msg = {"id": 3, "method": "submit",
           "params": {"seed": 7, "x_init": np.arange(12, dtype=np.float32)
                      .reshape(3, 4),
                      "mask": np.ones((2, 2), dtype=bool),
                      "nested": {"w": np.float64(2.5), "k": np.int64(9)},
                      "plain": [1, "two", None, 3.0]}}
    back = remote.decode_payload(remote.encode_payload(msg))
    assert back["id"] == 3 and back["method"] == "submit"
    np.testing.assert_array_equal(back["params"]["x_init"],
                                  msg["params"]["x_init"])
    assert back["params"]["x_init"].dtype == np.float32
    np.testing.assert_array_equal(back["params"]["mask"],
                                  msg["params"]["mask"])
    # numpy scalars cross as plain python numbers, not zero-d arrays
    assert back["params"]["nested"] == {"w": 2.5, "k": 9}
    assert back["params"]["plain"] == [1, "two", None, 3.0]


def test_frames_over_a_socket_and_eof_is_connection_error():
    a, b = socket.socketpair()
    try:
        remote.send_frame(a, {"event": "ticket",
                              "rows": np.zeros((2, 4), np.float32)})
        msg = remote.recv_frame(b)
        assert msg["event"] == "ticket" and msg["rows"].shape == (2, 4)
        a.close()
        with pytest.raises(ConnectionError):
            remote.recv_frame(b)
    finally:
        a.close()
        b.close()


def test_exception_round_trip_typed_with_cause():
    exc = DeadlineExceeded("ticket blew its 3s budget")
    exc.__cause__ = TimeoutError("socket timed out")
    back = decode_exception(encode_exception(exc))
    assert isinstance(back, DeadlineExceeded)
    assert "3s budget" in str(back)
    assert isinstance(back.__cause__, TimeoutError)


def test_exception_round_trip_unknown_type_degrades_typed():
    back = decode_exception({"type": "WeirdVendorError", "message": "boom"})
    assert isinstance(back, RequestFailedError)
    assert "[WeirdVendorError]" in str(back) and "boom" in str(back)


def test_params_npz_round_trip(tmp_path):
    params = {"encoder": {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
                          "b": np.zeros((3,), np.float32)},
              "head": {"scale": np.float32(0.5)}}
    path = remote.save_params_npz(str(tmp_path / "p.npz"), params)
    back = remote.load_params_npz(path)
    np.testing.assert_array_equal(back["encoder"]["w"],
                                  params["encoder"]["w"])
    np.testing.assert_array_equal(back["head"]["scale"], 0.5)


# ----------------------------------------------- drain-race satellite (local)


def test_local_replica_submit_after_drain_is_typed_not_runtime_error():
    """The Router snapshots health, then places — a replica draining in
    that window must raise the typed failover class (EngineClosedError →
    Router tries the next candidate), never a raw RuntimeError."""
    rep = fleet.LocalReplica(replica_main.StubEngine("local"))
    rep.warm([CFG], buckets=(4,), persistent_cache=False)
    rep.start()
    rep.drain(timeout=5)
    with pytest.raises(EngineClosedError, match="retry"):
        rep.submit(seed=0, n=1)


# -------------------------------------------------------- subprocess replica


def test_stub_subprocess_serves_bitwise_and_reports_health(reaper):
    rep = _spawn(reaper, spec={"stub": {"shape": list(STUB_SHAPE)}})
    rep.warm([CFG], buckets=(4, 8), persistent_cache=False)
    rep.start()
    with pytest.raises(ValueError, match="seed"):
        rep.submit(rng=object())
    t = rep.submit(seed=7, n=3)
    rows = t.result(timeout=15)
    np.testing.assert_array_equal(rows,
                                  replica_main.stub_rows(7, 3, STUB_SHAPE))
    h = rep.health()
    assert h["state"] == fleet.READY
    assert h["compiles_after_warmup"] == 0
    assert h["spawn_s"] > 0 and h["warm_s"] > 0
    rep.drain(timeout=10)
    assert rep.state == fleet.CLOSED
    assert rep._proc.poll() is not None, "drained child still running"


def test_kill_mid_batch_fails_queued_tickets_typed(reaper):
    """SIGKILL inside the child while two tickets sit queued: the in-flight
    RPC and both tickets all resolve typed, naming the replica — nothing
    blocks forever (the liveness contract)."""
    rep = _spawn(reaper, spec={"stub": {"delay_s": 0.5}},
                 env={"DDIM_COLD_FAULTS": "replica.kill:kill:at=2"})
    rep.warm([CFG], buckets=(4,), persistent_cache=False)
    rep.start()
    t1 = rep.submit(seed=1, n=2)
    t2 = rep.submit(seed=2, n=2)
    with pytest.raises((ReplicaCrashedError, ReplicaUnreachableError)):
        rep.submit(seed=3, n=1)  # the 3rd work frame pulls the trigger
    e1 = t1.exception(timeout=15)
    e2 = t2.exception(timeout=15)
    for e in (e1, e2):
        assert isinstance(e, ReplicaCrashedError), e
        assert "rk" in str(e), f"cause does not name the replica: {e}"
    assert _poll(lambda: rep.state == fleet.CLOSED)
    # whichever watcher won the race — reader EOF or the process waiter —
    # left its breadcrumb
    assert ("exited" in rep.crash_reason
            or "connection lost" in rep.crash_reason)
    report = rep.drain(timeout=5)  # retiring a corpse is a typed no-op
    assert report.get("crashed") is True


def test_heartbeat_loss_retires_hung_replica(reaper):
    """replica.hang wedges the child's reader thread (the process is alive
    but deaf): pings go unanswered, the miss budget empties, and the handle
    self-transitions to closed with the heartbeat breadcrumb."""
    rep = _spawn(reaper, spec={"stub": {}},
                 env={"DDIM_COLD_FAULTS": "replica.hang:hang:at=0,hang_s=60"},
                 heartbeat_s=0.15, miss_budget=3)
    rep.warm([CFG], buckets=(4,), persistent_cache=False)
    rep.start()
    with pytest.raises(ReplicaCrashedError, match="heartbeat"):
        rep.submit(seed=0, n=1)  # first work frame trips the wedge
    assert rep.state == fleet.CLOSED
    assert "heartbeat lost" in rep.crash_reason
    # the wedged child is ALIVE when the heartbeat budget empties — crash
    # handling must kill it, not just close the socket (a leaked child
    # would hold the accelerator against the respawned replacement)
    assert _poll(lambda: rep._proc.poll() is not None), \
        "heartbeat-loss crash leaked a live child process"
    rep.drain(timeout=5)  # retiring the corpse reaps it
    assert rep._proc.poll() is not None


def test_deadline_enforced_across_the_rpc_boundary(reaper):
    """deadline_s crosses the wire, expires inside the child, and the
    child's DeadlineExceeded comes back as the same type."""
    rep = _spawn(reaper, spec={"stub": {"delay_s": 0.5}})
    rep.warm([CFG], buckets=(4,), persistent_cache=False)
    rep.start()
    t = rep.submit(seed=0, n=1, deadline_s=0.05)
    exc = t.exception(timeout=15)
    assert isinstance(exc, DeadlineExceeded), exc
    rep.drain(timeout=10)


def test_rpc_drop_turns_into_unreachable_at_the_deadline(reaper):
    rep = _spawn(reaper, spec={"stub": {}}, rpc_timeout_s=0.5)
    rep.warm([CFG], buckets=(4,), persistent_cache=False)
    rep.start()
    with faults.inject(faults.FaultSpec(site="rpc.drop", kind="transient",
                                        match="method:health")):
        with pytest.raises(ReplicaUnreachableError, match="deadline"):
            rep.health()
    assert rep.health()["state"] == fleet.READY  # drop was the fault, not us
    rep.drain(timeout=10)


# ---------------------------------------------- protocol races and limits


class _FakeProc:
    """Popen lookalike for driving a RemoteReplica against a socketpair."""

    def __init__(self):
        self._dead = threading.Event()

    def wait(self, timeout=None):
        if not self._dead.wait(timeout):
            raise subprocess.TimeoutExpired("fake-replica", timeout)
        return 0

    def poll(self):
        return 0 if self._dead.is_set() else None

    def kill(self):
        self._dead.set()


def test_done_event_racing_ahead_of_submit_response_still_resolves():
    """The server's ticket done event can hit the wire BEFORE the submit
    RPC response (add_done_callback fires from the resolver thread for a
    fast request). The client registers the rid before the submit frame
    leaves, so the early event finds its ticket — an unknown-rid drop here
    would leave result() blocking forever on a healthy replica."""
    parent, child = socket.socketpair()
    proc = _FakeProc()
    rep = remote.RemoteReplica(parent, proc, replica_id="race",
                               heartbeat_s=60.0)
    try:
        rep.state = fleet.READY  # the fake server has no warm step
        rows = replica_main.stub_rows(3, 2, STUB_SHAPE)

        def server():
            msg = remote.recv_frame(child)
            rid = msg["params"]["rid"]
            # the racy interleaving, made deterministic: done event first,
            # submit response second
            remote.send_frame(child, {"event": "ticket", "rid": rid,
                                      "status": "done", "result": rows})
            remote.send_frame(child, {"id": msg["id"], "ok": True,
                                      "result": {"rid": rid, "n": 2}})

        th = threading.Thread(target=server, daemon=True)
        th.start()
        t = rep.submit(seed=3, n=2)
        np.testing.assert_array_equal(t.result(timeout=10), rows)
        th.join(5)
    finally:
        proc.kill()
        parent.close()
        child.close()


def test_oversized_submit_rejected_locally_replica_survives(
        reaper, monkeypatch):
    """An over-MAX_FRAME_BYTES submit raises typed at the CLIENT send site
    (RemoteRPCError — not retryable, so a hedge cannot replay it), and the
    replica it never reached keeps serving."""
    rep = _spawn(reaper, spec={"stub": {"shape": list(STUB_SHAPE)}})
    rep.warm([CFG], buckets=(4,), persistent_cache=False)
    rep.start()
    monkeypatch.setattr(remote, "MAX_FRAME_BYTES", 4096)
    with pytest.raises(RemoteRPCError, match="MAX_FRAME_BYTES"):
        rep.submit(seed=0, n=1,
                   x_init=np.zeros((1, 64, 64, 3), np.float32))
    monkeypatch.setattr(remote, "MAX_FRAME_BYTES", 1 << 30)
    assert rep.health()["state"] == fleet.READY
    t = rep.submit(seed=5, n=2)
    np.testing.assert_array_equal(t.result(timeout=15),
                                  replica_main.stub_rows(5, 2, STUB_SHAPE))
    rep.drain(timeout=10)


def test_server_drains_oversized_frame_and_keeps_serving(monkeypatch):
    """An over-limit INBOUND frame is not parent-gone: the server discards
    exactly the declared payload (stream stays framed), answers with a
    typed protocol_error event, and serves the next request — one bad
    frame must not os._exit a replica."""
    parent, child = socket.socketpair()
    try:
        srv = replica_main.ReplicaServer(child, replica=None,
                                         replica_id="lim")
        monkeypatch.setattr(remote, "MAX_FRAME_BYTES", 1024)
        parent.sendall(struct.pack(">I", 2048) + b"\x00" * 2048)
        remote.send_frame(parent, {"id": 2, "method": "ping", "params": {}})

        def server_turn():
            srv.handle(srv._recv_request())

        th = threading.Thread(target=server_turn, daemon=True)
        th.start()
        err_evt = remote.recv_frame(parent)
        assert err_evt["event"] == "protocol_error"
        assert "MAX_FRAME_BYTES" in err_evt["error"]["message"]
        pong = remote.recv_frame(parent)
        assert pong["id"] == 2 and pong["ok"]
        th.join(5)
    finally:
        parent.close()
        child.close()


# ------------------------------------------------------------ fleet failover


def test_router_failover_after_kill_is_bitwise_and_respawns(reaper):
    """The acceptance scenario at test scale: 2 subprocess replicas, r0
    SIGKILLed on its 2nd work frame mid-stream. Every ticket completes
    bitwise-identical to the deterministic stub rows (failover re-placed
    the dead replica's work), supervision spawns a replacement, and the
    fleet-wide compiles_after_warmup stays 0."""
    killed = {"DDIM_COLD_FAULTS": "replica.kill:kill:at=1,match=replica:r0|"}
    factory = remote.remote_factory({"backend": "stub",
                                     "stub": {"delay_s": 0.2}},
                                    env=killed, heartbeat_s=0.3,
                                    miss_budget=3)

    def tracking(rid):
        rep = factory(rid)
        reaper.append(rep)
        return rep

    router = Router(tracking, replicas=2, configs=(CFG,), buckets=(4, 8),
                    warm_kwargs=dict(persistent_cache=False),
                    drain_timeout_s=10, tick_s=0.02)
    try:
        tickets = [(seed, router.submit(seed=seed, n=2))
                   for seed in range(6)]
        for seed, t in tickets:
            np.testing.assert_array_equal(
                t.result(timeout=30),
                replica_main.stub_rows(seed, 2, STUB_SHAPE),
                err_msg=f"seed {seed} not bitwise after failover")
        assert _poll(lambda: router.health()["retired_replicas"] >= 1), \
            "the killed replica was never retired"
        assert _poll(lambda: router.health()["active_replicas"] == 2), \
            "no replacement spawned back to target"
        h = router.health()
        assert h["failovers"] >= 1
        assert h["compiles_after_warmup"] == 0
    finally:
        router.drain(timeout=15)
