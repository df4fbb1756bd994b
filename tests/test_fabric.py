"""repro.fabric cross-process tier: wire protocol codecs, registry
lease semantics, autoscaler hysteresis, windowed metrics, the
multi-process runtime helpers, and front-door routing/failover against
scripted fake workers (no jax partitions — the real end-to-end path is
the slow 2-process test at the bottom plus ``selftest --test fabric``).
"""
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.api import GraphSpec, PartitionRequest, Partitioner
from repro.api.runtime import (device_slices, distributed_init,
                               jax_backend_initialized)
from repro.core import PartitionerConfig
from repro.fabric import (AutoscaleConfig, AutoscalePolicy, FabricClient,
                          FrontDoor, ServerRegistry, pick_server)
from repro.fabric import protocol
from repro.serve import ServeMetrics

CFG = PartitionerConfig(contraction_limit=128, ip_repetitions=2,
                        num_chunks=4)


def tiny_request(n=60, k=2, seed=3):
    return PartitionRequest(graph=GraphSpec("rgg2d", n, 6.0, seed=seed),
                            k=k, config=CFG, backend="single")


# ---------------------------------------------------------------------------
# wire protocol
# ---------------------------------------------------------------------------

def test_framing_roundtrip_and_eof():
    a, b = socket.socketpair()
    try:
        protocol.send_msg(a, {"op": "ping", "x": [1, 2, 3]})
        assert protocol.recv_msg(b) == {"op": "ping", "x": [1, 2, 3]}
        a.close()
        # clean EOF at a frame boundary reads as None, not an error
        assert protocol.recv_msg(b) is None
    finally:
        b.close()


def test_framing_midframe_eof_is_protocol_error():
    a, b = socket.socketpair()
    try:
        # header promises 100 bytes, then the peer dies
        a.sendall(struct.pack(">I", 100) + b"abc")
        a.close()
        with pytest.raises(protocol.ProtocolError):
            protocol.recv_msg(b)
    finally:
        b.close()


def test_request_codec_spec_roundtrip():
    req = tiny_request()
    got = protocol.decode_request(protocol.encode_request(req))
    assert got.graph == req.graph  # GraphSpec is a frozen dataclass
    assert got.k == req.k and got.epsilon == req.epsilon
    assert got.preset == req.preset and got.seed == req.seed
    assert got.config == req.config and got.backend == "single"
    assert got.devices == req.devices
    assert got.collect_trace == req.collect_trace


def test_request_codec_graph_arrays_roundtrip():
    from repro.graphs import generators
    g = generators.make("rgg2d", 80, 6.0, seed=1)
    req = PartitionRequest(graph=g, k=2, config=CFG, backend="single",
                           contraction="sharded", weights="owner")
    got = protocol.decode_request(protocol.encode_request(req))
    for field in ("indptr", "adjncy", "eweights", "vweights"):
        want = getattr(g, field)
        have = getattr(got.graph, field)
        assert have.dtype == want.dtype
        assert np.array_equal(have, want)
    assert got.k == req.k and got.config == req.config
    assert got.contraction == "sharded" and got.weights == "owner"


def fake_ok(req, sid, assignment=None, cut=3):
    """A canned ok ServeResult wire dict, as a worker would send."""
    n = req.graph.n
    asg = np.arange(n, dtype=np.int64) % 2 if assignment is None \
        else assignment
    sr = SimpleNamespace(
        ok=True, error=None, detail="", worker=0, attempts=1, priority=0,
        queue_wait_s=0.001, total_s=0.01,
        result=SimpleNamespace(assignment=asg, cut=cut, feasible=True,
                               backend="fake", time_s=0.01,
                               metrics={"n": np.int64(n)}))
    return protocol.encode_serve_result(sr, sid)


def test_result_codec_roundtrip():
    req = tiny_request()
    wire = fake_ok(req, "srv-a")
    res = protocol.decode_result(wire)
    assert res.ok and res.server == "srv-a" and res.cut == 3
    assert res.assignment.dtype == np.int64
    assert np.array_equal(res.assignment,
                          np.arange(req.graph.n, dtype=np.int64) % 2)
    assert res.metrics == {"n": req.graph.n}  # numpy scalar stripped

    err = protocol.decode_result(
        protocol.error_result("worker_failed", "boom", attempts=2))
    assert not err.ok and err.error == "worker_failed"
    assert err.attempts == 2 and err.assignment is None
    assert err.summary()["error"] == "worker_failed"


# ---------------------------------------------------------------------------
# registry leases (fake clock)
# ---------------------------------------------------------------------------

class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_lease_register_renew_expire_timing():
    clk = Clock()
    reg = ServerRegistry(ttl_s=5.0, clock=clk)
    rec = reg.register("w0", "127.0.0.1", 1234, devices=2, meshes=3)
    assert rec.lease_expiry == 105.0 and rec.generation == 0
    assert [r.server_id for r in reg.alive()] == ["w0"]
    clk.t = 104.0
    assert reg.renew("w0", metrics={"inflight": 1})
    assert reg.get("w0").lease_expiry == 109.0
    assert reg.get("w0").renewals == 1
    assert reg.get("w0").metrics == {"inflight": 1}
    # no renewals past the new expiry: the lease lapses
    clk.t = 109.0
    assert reg.alive() == []
    dead = reg.expire()
    assert [r.server_id for r in dead] == ["w0"]
    assert reg.expire() == []  # expiry removes; a second sweep is empty


def test_renew_after_expiry_is_false_then_reregister_bumps_generation():
    clk = Clock()
    reg = ServerRegistry(ttl_s=2.0, clock=clk)
    reg.register("w0", "h", 1)
    clk.t += 3.0
    # the worker's cue to re-register: renew refuses a lapsed lease
    assert not reg.renew("w0")
    assert not reg.renew("never-registered")
    rec = reg.register("w0", "h", 2)
    assert rec.generation == 1 and rec.port == 2
    rec = reg.register("w0", "h", 3)
    assert rec.generation == 2


def test_expire_removes_only_lapsed_and_alive_is_sorted():
    clk = Clock()
    reg = ServerRegistry(ttl_s=5.0, clock=clk)
    reg.register("b", "h", 1)
    clk.t += 3.0
    reg.register("a", "h", 2)
    clk.t += 3.0  # b lapsed (6s), a still warm (3s)
    assert [r.server_id for r in reg.expire()] == ["b"]
    assert [r.server_id for r in reg.alive()] == ["a"]
    assert len(reg) == 1
    assert reg.deregister("a").server_id == "a"
    assert reg.deregister("a") is None


# ---------------------------------------------------------------------------
# autoscaler policy hysteresis (pure)
# ---------------------------------------------------------------------------

def test_policy_grows_only_after_consecutive_pressure_windows():
    pol = AutoscalePolicy(AutoscaleConfig(
        min_workers=1, max_workers=3, grow_queue_depth=2.0,
        grow_windows=2, shrink_windows=4))
    assert pol.observe(workers=1, queue_depth=5) == 0  # 1st breach
    assert pol.observe(workers=1, queue_depth=0, submitted=1) == 0  # reset
    assert pol.observe(workers=1, queue_depth=5) == 0
    assert pol.observe(workers=1, queue_depth=5) == 1  # 2nd in a row
    # pressure is per worker: depth 3 over 2 workers is no breach
    assert pol.observe(workers=2, queue_depth=3) == 0
    assert pol.observe(workers=2, queue_depth=3) == 0


def test_policy_deadline_miss_is_always_a_breach():
    pol = AutoscalePolicy(AutoscaleConfig(grow_windows=2, max_workers=2))
    assert pol.observe(workers=1, queue_depth=0, deadline_misses=1) == 0
    assert pol.observe(workers=1, queue_depth=0, deadline_misses=1) == 1


def test_policy_shrinks_after_idle_windows_within_bounds():
    pol = AutoscalePolicy(AutoscaleConfig(
        min_workers=1, max_workers=3, shrink_windows=3))
    for _ in range(2):
        assert pol.observe(workers=2, queue_depth=0) == 0
    assert pol.observe(workers=2, queue_depth=0) == -1
    # at min_workers the fleet never shrinks, however idle
    for _ in range(10):
        assert pol.observe(workers=1, queue_depth=0) == 0
    # inflight work is not idle
    for _ in range(10):
        assert pol.observe(workers=2, queue_depth=0, inflight=1) == 0


def test_policy_never_grows_past_max():
    pol = AutoscalePolicy(AutoscaleConfig(max_workers=2, grow_windows=1))
    assert pol.observe(workers=1, queue_depth=9) == 1
    assert pol.observe(workers=2, queue_depth=9) == 0


def test_autoscale_config_validates():
    with pytest.raises(ValueError):
        AutoscaleConfig(min_workers=0).validate()
    with pytest.raises(ValueError):
        AutoscaleConfig(min_workers=3, max_workers=2).validate()
    with pytest.raises(ValueError):
        AutoscaleConfig(eval_period_s=0.0).validate()


# ---------------------------------------------------------------------------
# scheduler: server-granularity routing (pure)
# ---------------------------------------------------------------------------

def S(sid, devices=1, inflight=0):
    return SimpleNamespace(sid=sid, devices=devices, inflight=inflight)


def test_pick_server_exact_fit_load_then_sid():
    assert pick_server(4, [S("a", 8), S("b", 4)]).sid == "b"  # exact
    assert pick_server(2, [S("a", 8), S("b", 4)]).sid == "b"  # smallest fit
    assert pick_server(1, [S("a", 1, inflight=2), S("b", 1)]).sid == "b"
    assert pick_server(1, [S("b", 1), S("a", 1)]).sid == "a"  # sid tiebreak
    assert pick_server(1, []) is None


# ---------------------------------------------------------------------------
# windowed metrics (satellite)
# ---------------------------------------------------------------------------

def test_snapshot_window_deltas_reset_between_reads():
    m = ServeMetrics(2)
    m.on_submit(1)
    m.on_dispatch(0)
    m.on_done(True, latency_s=0.2, queue_wait_s=0.01, worker=0)
    win = m.snapshot_window()
    assert win["submitted"] == 1 and win["completed"] == 1
    assert win["failed"] == 0
    assert win["latency_p99_s"] == pytest.approx(0.2)
    assert win["queue_depth_max"] >= 1
    # a second read covers only what happened since the first
    win2 = m.snapshot_window()
    assert win2["submitted"] == 0 and win2["completed"] == 0
    assert win2["latency_p99_s"] == 0.0
    m.on_submit(3)
    assert m.snapshot_window()["submitted"] == 1
    # cumulative snapshot is untouched by window reads
    assert m.snapshot()["submitted"] == 2


def test_per_worker_served_grows_for_late_workers():
    m = ServeMetrics(1)
    m.on_done(True, 0.1, 0.0, worker=0)
    m.on_done(True, 0.1, 0.0, worker=3)  # a server joined after startup
    assert m.snapshot()["per_worker_served"] == [1, 0, 0, 1]
    m.resize_workers(6)
    assert len(m.snapshot()["per_worker_served"]) == 6
    m.resize_workers(2)  # grow-only: never forgets a server's tally
    assert len(m.snapshot()["per_worker_served"]) == 6


# ---------------------------------------------------------------------------
# runtime helpers (satellites)
# ---------------------------------------------------------------------------

def test_process_scaler_caps_chip_workers_and_keeps_their_logs(
        tmp_path, monkeypatch):
    from repro.fabric import autoscaler

    started = []

    class FakeWorker:  # stays alive until closed
        pid = 4242

        def __init__(self, cmd, env, stdout, stderr):
            started.append((cmd, stdout.name, stderr))

        def poll(self):
            return None

        def send_signal(self, sig):
            pass

        def wait(self, timeout=None):
            return 0

    monkeypatch.setattr(autoscaler.subprocess, "Popen", FakeWorker)
    uncapped = autoscaler.ProcessScaler([], env={"JAX_PLATFORMS": "cpu"},
                                        log_dir=str(tmp_path))
    assert uncapped.worker_cap is None
    # a host with a TPU: every worker would open all of its chips
    monkeypatch.setattr(autoscaler, "_workers_hold_chips", lambda env: True)
    scaler = autoscaler.ProcessScaler(["--frontdoor", "127.0.0.1:1"],
                                      log_dir=str(tmp_path))
    assert scaler.worker_cap == 1
    sid = scaler.scale_up()
    assert sid is not None
    assert scaler.scale_up() is None      # the one chip slot is taken
    assert scaler.count() == 1
    cmd, log, err = started[0]
    assert cmd[-2:] == ["--frontdoor", "127.0.0.1:1"]
    assert log == str(tmp_path / f"{sid}.log")
    assert err is subprocess.STDOUT
    scaler.close()
    assert scaler.count() == 0


def test_device_slices_error_names_counts_and_feasible_carve():
    import jax
    have = len(jax.devices())
    with pytest.raises(RuntimeError) as ei:
        device_slices(have + 1, 4)
    msg = str(ei.value)
    assert f"only {have} device(s) available" in msg
    assert ("largest feasible" in msg) or ("no carve" in msg)
    with pytest.raises(ValueError):
        device_slices(0, 1)


def test_distributed_init_single_process_noop():
    info = distributed_init()
    assert info == {"mode": "single-process", "process_id": 0,
                    "num_processes": 1}


def test_distributed_init_env_fallback_single(monkeypatch):
    monkeypatch.setenv("REPRO_NUM_PROCESSES", "1")
    monkeypatch.delenv("REPRO_COORDINATOR", raising=False)
    assert distributed_init()["mode"] == "single-process"


def test_distributed_init_validates_ranks():
    with pytest.raises(ValueError):
        distributed_init(coordinator_address="127.0.0.1:9",
                         num_processes=2, process_id=5)


def test_distributed_init_refuses_initialized_backend():
    import jax
    jax.devices()  # make sure a backend exists in this process
    assert jax_backend_initialized()
    with pytest.raises(RuntimeError):
        distributed_init(coordinator_address="127.0.0.1:9",
                         num_processes=2, process_id=0)


# ---------------------------------------------------------------------------
# front door vs scripted fake workers (real sockets, no jax partitions)
# ---------------------------------------------------------------------------

class FakeWorker:
    """A scripted fabric server: registers with the front door over a
    real heartbeat connection and answers ``partition`` frames with
    whatever ``handler(msg, conn) -> wire dict | None`` returns (None =
    stay silent; the handler may also close ``conn`` to fake a crash).
    """

    def __init__(self, fd_addr, sid, handler, *, devices=1, meshes=1,
                 renew=True, heartbeat_s=0.1):
        self.sid = sid
        self.handler = handler
        self._renew = renew
        self._heartbeat_s = heartbeat_s
        self._fd_addr = fd_addr
        self._stop = threading.Event()
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(8)
        self.host, self.port = self._listener.getsockname()[:2]
        self._devices, self._meshes = devices, meshes
        threading.Thread(target=self._accept, daemon=True).start()
        threading.Thread(target=self._heartbeat, daemon=True).start()

    def _accept(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        try:
            while True:
                msg = protocol.recv_msg(conn)
                if msg is None:
                    return
                if msg.get("op") != "partition":
                    continue
                wire = self.handler(msg, conn)
                if wire is not None:
                    protocol.send_msg(conn, {"op": "result",
                                             "id": msg["id"],
                                             "result": wire})
        except (OSError, protocol.ProtocolError):
            return
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _heartbeat(self):
        try:
            sock = protocol.connect(*self._fd_addr, timeout=5.0)
            protocol.send_msg(sock, {
                "op": "register",
                "server": {"server_id": self.sid, "host": self.host,
                           "port": self.port, "devices": self._devices,
                           "meshes": self._meshes, "pid": 0}})
            protocol.recv_msg(sock)
            while self._renew and not self._stop.wait(self._heartbeat_s):
                protocol.send_msg(sock, {"op": "renew",
                                         "server_id": self.sid})
                protocol.recv_msg(sock)
            sock.close()
        except (OSError, protocol.ProtocolError):
            pass

    def stop(self):
        self._stop.set()
        try:
            self._listener.close()
        except OSError:
            pass


def wait_for_servers(fd, count, timeout=10.0):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        with fd._cond:
            live = sum(1 for h in fd._handles.values() if h.alive)
        if live >= count:
            return
        time.sleep(0.01)
    raise AssertionError(f"{count} server(s) never connected")


def test_frontdoor_routes_and_decodes():
    req = tiny_request()
    with FrontDoor(port=0, lease_ttl_s=2.0) as fd:
        w = FakeWorker((fd.host, fd.port), "a",
                       lambda m, c: fake_ok(
                           protocol.decode_request(m["request"]), "a"))
        try:
            wait_for_servers(fd, 1)
            with FabricClient(fd.host, fd.port) as client:
                res = client.submit(req).result(timeout=30)
                assert res.ok and res.server == "a"
                assert res.attempts == 1
                assert np.array_equal(
                    res.assignment,
                    np.arange(req.graph.n, dtype=np.int64) % 2)
                st = client.status()
                assert [s["server_id"] for s in st["servers"]] == ["a"]
        finally:
            w.stop()


def test_frontdoor_reroutes_on_server_closed_reply():
    req = tiny_request()
    with FrontDoor(port=0, lease_ttl_s=2.0) as fd:
        bad = FakeWorker((fd.host, fd.port), "a-bad",
                         lambda m, c: protocol.error_result(
                             "server_closed", "draining"))
        good = FakeWorker((fd.host, fd.port), "b-good",
                          lambda m, c: fake_ok(
                              protocol.decode_request(m["request"]),
                              "b-good"))
        try:
            wait_for_servers(fd, 2)
            with FabricClient(fd.host, fd.port) as client:
                # sid tiebreak routes to "a-bad" first; its structured
                # refusal re-routes to "b-good"
                res = client.submit(req).result(timeout=30)
                assert res.ok and res.server == "b-good"
                assert res.attempts == 2
        finally:
            bad.stop()
            good.stop()


def test_frontdoor_fails_over_on_connection_loss():
    req = tiny_request()

    def crash(msg, conn):
        conn.close()  # drop the work connection mid-request
        return None

    with FrontDoor(port=0, lease_ttl_s=2.0) as fd:
        bad = FakeWorker((fd.host, fd.port), "a-bad", crash)
        good = FakeWorker((fd.host, fd.port), "b-good",
                          lambda m, c: fake_ok(
                              protocol.decode_request(m["request"]),
                              "b-good"))
        try:
            wait_for_servers(fd, 2)
            with FabricClient(fd.host, fd.port) as client:
                res = client.submit(req).result(timeout=30)
                assert res.ok and res.server == "b-good"
                assert res.attempts == 2
        finally:
            bad.stop()
            good.stop()


def test_frontdoor_reroutes_from_expired_lease():
    req = tiny_request()
    with FrontDoor(port=0, lease_ttl_s=0.6) as fd:
        # "a-dead" accepts the request, never answers, never renews:
        # only the lease expiry can rescue its ticket
        dead = FakeWorker((fd.host, fd.port), "a-dead",
                          lambda m, c: None, renew=False)
        good = FakeWorker((fd.host, fd.port), "b-good",
                          lambda m, c: fake_ok(
                              protocol.decode_request(m["request"]),
                              "b-good"),
                          heartbeat_s=0.1)
        try:
            wait_for_servers(fd, 2)
            with FabricClient(fd.host, fd.port) as client:
                t0 = time.monotonic()
                res = client.submit(req).result(timeout=30)
                assert res.ok and res.server == "b-good"
                assert res.attempts == 2
                # rescued by expiry, not by a slow client timeout
                assert time.monotonic() - t0 < 10.0
            assert fd.registry.get("a-dead") is None
        finally:
            dead.stop()
            good.stop()


def test_frontdoor_no_worker_when_retries_exhausted():
    req = tiny_request()
    with FrontDoor(port=0, lease_ttl_s=2.0, max_retries=1) as fd:
        bad = FakeWorker((fd.host, fd.port), "only",
                         lambda m, c: protocol.error_result(
                             "worker_failed", "boom"))
        try:
            wait_for_servers(fd, 1)
            with FabricClient(fd.host, fd.port) as client:
                res = client.submit(req).result(timeout=30)
                assert not res.ok and res.error == "no_worker"
                assert "boom" in res.detail
        finally:
            bad.stop()


def test_frontdoor_fresh_ticket_waits_then_deadline():
    # zero registered servers: a fresh ticket is NOT no_worker'd (a
    # worker may register any moment) — its deadline still binds
    req = tiny_request()
    with FrontDoor(port=0, lease_ttl_s=2.0) as fd:
        with FabricClient(fd.host, fd.port) as client:
            res = client.submit(req, deadline_s=0.3).result(timeout=30)
            assert not res.ok and res.error == "deadline_exceeded"


def test_frontdoor_rejects_malformed_request():
    with FrontDoor(port=0, lease_ttl_s=2.0) as fd:
        sock = protocol.connect(fd.host, fd.port, timeout=5.0)
        try:
            protocol.send_msg(sock, {"op": "partition", "id": 7,
                                     "request": {"graph": {"kind": "?"}}})
            resp = protocol.recv_msg(sock)
            assert resp["op"] == "result" and resp["id"] == 7
            assert resp["result"]["error"] == "rejected"
        finally:
            sock.close()


def test_client_connection_loss_is_structured():
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    host, port = lst.getsockname()[:2]
    accepted = []

    def accept_then_hang():
        conn, _ = lst.accept()
        accepted.append(conn)

    threading.Thread(target=accept_then_hang, daemon=True).start()
    client = FabricClient(host, port)
    try:
        fut = client.submit(tiny_request())
        t_end = time.monotonic() + 5
        while not accepted and time.monotonic() < t_end:
            time.sleep(0.01)
        accepted[0].close()  # the "front door" dies mid-request
        res = fut.result(timeout=30)
        assert not res.ok and res.error == "connection_lost"
    finally:
        client.close()
        lst.close()


# ---------------------------------------------------------------------------
# 2-process end-to-end (slow: spawns a real worker subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_two_process_bit_identity_and_drain():
    import repro
    src_dir = os.path.dirname(os.path.dirname(repro.__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_dir + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    reqs = [PartitionRequest(
        graph=GraphSpec("rgg2d", 400 + 100 * i, 6.0, seed=2 + i),
        k=2 + i % 2, config=CFG) for i in range(3)]
    solo = [Partitioner().run(r) for r in reqs]
    with FrontDoor(port=0, lease_ttl_s=3.0) as fd:
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.fabric", "worker",
             "--frontdoor", f"{fd.host}:{fd.port}",
             "--server-id", "t2p", "--heartbeat-s", "0.3"],
            stdout=subprocess.PIPE, env=env, text=True)
        try:
            ready = json.loads(proc.stdout.readline())
            assert ready["op"] == "ready" and ready["server_id"] == "t2p"
            wait_for_servers(fd, 1, timeout=60)
            with FabricClient(fd.host, fd.port) as client:
                rs = client.serve(reqs)
            assert all(r.ok and r.server == "t2p" for r in rs)
            for r, s in zip(rs, solo):
                assert np.array_equal(r.assignment, s.assignment)
                assert r.cut == s.cut
            # graceful drain: SIGTERM deregisters and exits cleanly
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=60) == 0
            t_end = time.monotonic() + 10
            while fd.registry.get("t2p") and time.monotonic() < t_end:
                time.sleep(0.05)
            assert fd.registry.get("t2p") is None
        finally:
            if proc.poll() is None:
                proc.kill()
