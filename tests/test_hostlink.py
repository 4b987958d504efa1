"""Multi-host slab transport tests (``spec.transport = "host"``).

Three layers:

  * **pinned wire format** — slab payloads are little-endian ``<f4`` on
    encode AND decode (a byteswapped input round-trips to the same
    values; the wire bytes are LE regardless of the input's order), the
    HELLO handshake carries magic + protocol version, and malformed /
    mismatched / oversized peers are rejected with a readable, logged
    error instead of being misparsed as workers;
  * **addressing + leader discovery** — explicit ``--listen`` ports
    (with SO_REUSEADDR fast restart), JOIN/WELCOME worker-id leases
    with generation fencing, and the spec travelling over the wire;
  * **end to end** — a leader plus two *separately launched*
    ``python -m repro join`` process groups (distinct interpreters,
    distinct spec-JSON rebuilds, TCP the only link) is bitwise
    identical to ``inproc`` under a sync gradient budget, and joined
    workers exit cleanly (EOF, no strand) when the leader dies.
"""
import logging
import socket
import struct
import threading
import time

import numpy as np
import pytest

import jax

from repro.api import ExperimentSpec
from repro.cluster import mptransport as mpt
from repro.cluster.hostlink import (HostTransport, negotiate_join,
                                    parse_hostport, spawn_join_process)
from repro.cluster.mptransport import (SocketTransport,
                                       SocketWorkerClient,
                                       WireProtocolError)
from repro.cluster.trainer import ClusterTrainer
from repro.cluster.transport import GradientMsg, ParamsMsg

# joined/spawned worker process groups must not fight the parent for an
# exclusive accelerator (same rule as the proc transport's children)
CHILD_PLATFORM = None if jax.default_backend() == "cpu" else "cpu"


def _poll(predicate, timeout_s: float = 5.0, what: str = "condition"):
    deadline = time.monotonic() + timeout_s
    while not predicate():
        assert time.monotonic() < deadline, f"timed out waiting: {what}"
        time.sleep(0.02)


# ------------------------------------------------------------ addressing

def test_parse_hostport():
    assert parse_hostport("10.0.0.7:5555") == ("10.0.0.7", 5555)
    assert parse_hostport(":0") == ("127.0.0.1", 0)
    assert parse_hostport("7781") == ("127.0.0.1", 7781)
    with pytest.raises(ValueError, match="HOST:PORT"):
        parse_hostport("nonsense:port")
    with pytest.raises(ValueError, match="port"):
        parse_hostport("h:70000")


def test_tcp_explicit_port_resolved_and_fast_restart():
    """An explicit port binds that port (0 still means "pick"), the
    resolved address is exposed, and SO_REUSEADDR lets a fast restart
    rebind the same port while old connections sit in TIME_WAIT."""
    t1 = SocketTransport(2, family="tcp", port=0)
    host, port = tuple(t1.address)
    assert port != 0
    # leave a connection behind so the close puts the server side in
    # TIME_WAIT — the state a non-REUSEADDR rebind trips over
    c1 = t1.connect(0)
    assert t1.wait_for_workers(1, timeout=5.0)
    c1.close()
    t1.close()
    t2 = SocketTransport(2, family="tcp", port=port)    # immediate rebind
    try:
        assert tuple(t2.address) == (host, port)
        c2 = t2.connect(1)
        assert t2.wait_for_workers(1, timeout=5.0)
        c2.close()
    finally:
        t2.close()


def test_spec_host_transport_round_trip_and_listen_validation():
    spec = ExperimentSpec(transport="host", listen="0.0.0.0:5555",
                          backend="cluster")
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    with pytest.raises(ValueError, match="listen"):
        ExperimentSpec(transport="host", listen="not-an-address:x")


# ------------------------------------------------- pinned slab byte order

def test_slab_payload_is_little_endian_on_the_wire():
    """Encode pins ``<f4``: a byteswapped (big-endian) input produces
    the exact same wire bytes as the native little-endian one."""
    vals = np.linspace(-3.0, 7.0, 16, dtype=np.float32)
    swapped = vals.astype(">f4")            # same values, swapped bytes
    goff = mpt._HDR.size + mpt._GRAD.size
    for arr in (vals, swapped):
        frame = mpt._grad_frame(GradientMsg(3, arr, 7, 1))
        assert frame[goff:] == vals.astype("<f4").tobytes()
    poff = mpt._HDR.size + mpt._PARAMS.size
    for arr in (vals, swapped):
        frame = mpt._params_frame(ParamsMsg(5, arr, epoch=2))
        assert frame[poff:] == vals.astype("<f4").tobytes()


def test_byteswapped_payload_roundtrips_over_socket():
    """The regression the multi-host boundary demands: a gradient
    handed over as a byteswapped buffer arrives value-identical and in
    the *native* dtype (decode is explicit ``<f4``, normalized)."""
    hub = SocketTransport(4, family="tcp")
    client = hub.connect(0)
    try:
        vals = np.linspace(-1.0, 1.0, 32, dtype=np.float32)
        assert client.send_gradient(
            GradientMsg(0, vals.astype(">f4"), 1, 1), timeout=5.0)
        msg = hub.recv_gradient(timeout=5.0)
        assert msg is not None
        got = np.asarray(msg.grad)
        assert got.dtype == np.float32 and got.dtype.isnative
        assert got.tobytes() == vals.tobytes()      # bitwise, post-pin
        # and the broadcast direction
        hub.publish_params(ParamsMsg(1, vals.astype(">f4")))
        pmsg = client.fetch_params(min_version=1, timeout=5.0)
        assert pmsg is not None
        pgot = np.asarray(pmsg.params)
        assert pgot.dtype == np.float32 and pgot.dtype.isnative
        assert pgot.tobytes() == vals.tobytes()
    finally:
        client.close()
        hub.close()


# ------------------------------------------------ handshake gatekeeping

def test_garbage_connection_rejected_without_joining_barrier():
    """A stray TCP client (here: speaking HTTP) must be turned away —
    logged and counted — without crashing the hub, entering the fleet
    barrier, or wedging a reader on a garbage frame length."""
    hub = SocketTransport(4, family="tcp")
    try:
        stray = socket.create_connection(tuple(hub.address), timeout=5.0)
        stray.sendall(b"GET / HTTP/1.1\r\nHost: example\r\n\r\n")
        _poll(lambda: hub.rejected_peers == 1, what="stray rejected")
        assert hub.live_workers() == set()
        assert not hub.wait_for_workers(1, timeout=0.2)
        # the stray sees the connection die (EOF or RST, possibly after
        # a REJECT frame it cannot parse) — never a hang
        stray.settimeout(5.0)
        try:
            while stray.recv(65536):
                pass
        except OSError:
            pass        # RST: the hub closed with unread bytes pending
        stray.close()
        # the hub still serves legitimate peers afterwards
        client = hub.connect(0)
        assert hub.wait_for_workers(1, timeout=5.0)
        client.close()
    finally:
        hub.close()


def test_hello_version_mismatch_rejected_with_readable_error(caplog):
    """Right magic, wrong protocol version: the peer gets a REJECT
    frame with a human-readable reason, the hub logs it, and the
    connection never becomes a worker."""
    hub = SocketTransport(4, family="tcp")
    try:
        peer = socket.create_connection(tuple(hub.address), timeout=5.0)
        bad = (mpt._HDR.pack(mpt._F_HELLO, mpt._HELLO.size)
               + mpt._HELLO.pack(mpt._MAGIC, 99, 0, 0))
        with caplog.at_level(logging.WARNING):
            peer.sendall(bad)
            _poll(lambda: hub.rejected_peers == 1, what="peer rejected")
        assert "version mismatch" in caplog.text and "v99" in caplog.text
        peer.settimeout(5.0)
        hdr = peer.recv(mpt._HDR.size, socket.MSG_WAITALL)
        ftype, n = mpt._HDR.unpack(hdr)
        assert ftype == mpt._F_REJECT
        payload = peer.recv(n, socket.MSG_WAITALL)
        reason = payload[mpt._CTRL.size:].decode()
        assert "version mismatch" in reason and "v99" in reason
        peer.close()
        assert hub.live_workers() == set()
    finally:
        hub.close()


def test_bad_magic_and_oversized_frame_rejected():
    hub = SocketTransport(4, family="tcp")
    try:
        # wrong magic in an otherwise well-formed HELLO
        p1 = socket.create_connection(tuple(hub.address), timeout=5.0)
        p1.sendall(mpt._HDR.pack(mpt._F_HELLO, mpt._HELLO.size)
                   + mpt._HELLO.pack(0xDEADBEEF, mpt._PROTO_VERSION,
                                     0, 0))
        _poll(lambda: hub.rejected_peers == 1, what="bad magic rejected")
        p1.close()
        # an authenticated peer that loses frame sync (absurd length)
        # is cut off before the reader commits to the garbage read
        p2 = socket.create_connection(tuple(hub.address), timeout=5.0)
        p2.sendall(mpt._hello_frame(1, 0))
        _poll(lambda: 1 in hub.live_workers(), what="worker 1 admitted")
        p2.sendall(mpt._HDR.pack(mpt._F_GRAD, mpt._MAX_FRAME + 1))
        _poll(lambda: hub.rejected_peers == 2, what="oversize rejected")
        _poll(lambda: hub.live_workers() == set(),
              what="worker 1 deregistered")
        p2.close()
        # a GRAD whose slab is not whole f4 elements is rejected with a
        # readable error too — never an unhandled reader crash
        p3 = socket.create_connection(tuple(hub.address), timeout=5.0)
        p3.sendall(mpt._hello_frame(2, 0))
        _poll(lambda: 2 in hub.live_workers(), what="worker 2 admitted")
        p3.sendall(mpt._HDR.pack(mpt._F_GRAD, mpt._GRAD.size + 3)
                   + b"\x00" * (mpt._GRAD.size + 3))
        _poll(lambda: hub.rejected_peers == 3,
              what="ragged GRAD rejected")
        p3.close()
    finally:
        hub.close()


def test_silent_peer_receives_no_params_broadcast():
    """A connection that never authenticates must not receive the
    model: the params broadcast is gated on a valid HELLO, so a silent
    stray peer gets nothing while real workers still get every
    publish."""
    hub = SocketTransport(4, family="tcp")
    silent = None
    try:
        silent = socket.create_connection(tuple(hub.address),
                                          timeout=5.0)
        time.sleep(0.3)     # writer thread is up; peer stays silent
        hub.publish_params(ParamsMsg(1, np.ones(64, np.float32)))
        client = hub.connect(0)
        msg = client.fetch_params(min_version=1, timeout=5.0)
        assert msg is not None and msg.version == 1   # workers: yes
        silent.settimeout(1.0)
        try:
            got = silent.recv(4096)
        except socket.timeout:
            got = b""
        assert got == b"", "stray peer received broadcast bytes"
        client.close()
    finally:
        if silent is not None:
            silent.close()
        hub.close()


def test_out_of_range_hello_rejected():
    """A direct HELLO naming a worker id outside the fleet must not be
    admitted — it would satisfy the fleet-ready barrier while its data
    shard does not exist."""
    hub = HostTransport(4, host="127.0.0.1", port=0, num_workers=2,
                        welcome_config={})
    try:
        stray = SocketWorkerClient(tuple(hub.address), 7, generation=0,
                                   family="tcp")
        assert stray.closed.wait(5.0)
        assert "out of range" in (stray.reject_reason or "")
        stray.close()
        assert hub.live_workers() == set()
    finally:
        hub.close()


def test_rehello_rejected_and_no_ghost_registration():
    """One connection identifies itself exactly once: a second HELLO
    (e.g. under a different worker id) is a protocol violation.  The
    misbehaving connection is dropped whole, so the barrier never keeps
    a ghost worker id that no connection backs."""
    hub = SocketTransport(4, family="tcp")
    gone = []
    hub.on_worker_gone = lambda wid, gen: gone.append(wid)
    try:
        peer = socket.create_connection(tuple(hub.address), timeout=5.0)
        peer.sendall(mpt._hello_frame(0, 0))
        _poll(lambda: 0 in hub.live_workers(), what="worker 0 admitted")
        peer.sendall(mpt._hello_frame(1, 0))       # re-HELLO, new id
        _poll(lambda: hub.rejected_peers == 1, what="re-HELLO rejected")
        _poll(lambda: hub.live_workers() == set(),
              what="no ghost worker left behind")
        assert gone == [0]      # the original id was deregistered
        peer.close()
    finally:
        hub.close()


def test_client_surfaces_reject_reason():
    """A fenced/rejected worker endpoint closes with the hub's readable
    reason on ``reject_reason`` instead of spinning."""
    hub = HostTransport(4, host="127.0.0.1", port=0, num_workers=2,
                        welcome_config={})
    live = hub.connect(1)
    try:
        assert hub.wait_for_workers(1, timeout=5.0)
        dup = hub.connect(1)        # same worker id, same generation
        assert dup.closed.wait(5.0)
        assert "live connection" in (dup.reject_reason or "")
        dup.close()
    finally:
        live.close()
        hub.close()


# --------------------------------------------------- leases and fencing

def test_join_lease_negotiation_and_generation_fencing():
    hub = HostTransport(4, host="127.0.0.1", port=0, num_workers=2,
                        welcome_config={"spec": {"arch": "mlp"}})
    addr = tuple(hub.address)
    socks = []
    try:
        s0, cfg0 = negotiate_join(addr)
        socks.append(s0)
        assert (cfg0["worker_id"], cfg0["generation"]) == (0, 0)
        assert cfg0["num_workers"] == 2
        assert cfg0["spec"] == {"arch": "mlp"}      # the wire contract

        # the lease window is protected: worker 0 is leased but still
        # "compiling" (no HELLO yet) — a direct HELLO for its id at the
        # current generation must not steal the shard from under it
        impostor = SocketWorkerClient(addr, 0, generation=0,
                                      family="tcp")
        assert impostor.closed.wait(5.0)
        assert "live connection" in (impostor.reject_reason or "")
        impostor.close()
        s1, cfg1 = negotiate_join(addr)
        socks.append(s1)
        assert (cfg1["worker_id"], cfg1["generation"]) == (1, 0)
        # lease contention is retried within connect_timeout (it can
        # resolve as the fleet churns), so expecting the failure needs
        # a short deadline; an out-of-range id fails immediately
        with pytest.raises(WireProtocolError, match="full"):
            negotiate_join(addr, connect_timeout=0.5)
        with pytest.raises(WireProtocolError, match="already joined"):
            negotiate_join(addr, worker_id=1, connect_timeout=0.5)
        t0 = time.monotonic()
        with pytest.raises(WireProtocolError, match="out of range"):
            negotiate_join(addr, worker_id=5, connect_timeout=30.0)
        assert time.monotonic() - t0 < 5.0      # permanent: no retry

        # a rejoining host resumes its shard (same worker id), fenced
        # by a bumped generation — not a duplicate.  The rejoin may
        # race the hub reaping the dead predecessor's connection;
        # negotiate_join retries that transient rejection itself
        s1.close()
        s1b, cfg1b = negotiate_join(addr, worker_id=1,
                                    connect_timeout=10.0)
        socks.append(s1b)
        assert (cfg1b["worker_id"], cfg1b["generation"]) == (1, 1)

        # generation fencing: even with NO live connection holding the
        # id (the lease record outlives the connection), a HELLO from
        # the superseded generation-0 peer is turned away
        s1b.close()
        deadline = time.monotonic() + 5.0
        while True:
            stale = SocketWorkerClient(addr, 1, generation=0,
                                       family="tcp")
            assert stale.closed.wait(5.0)
            reason = stale.reject_reason or ""
            stale.close()
            if "generation fence" in reason:
                break
            # the hub may not have reaped s1b's connection yet, in
            # which case the (also correct) duplicate rejection fires
            assert "live connection" in reason, reason
            assert time.monotonic() < deadline
            time.sleep(0.05)
    finally:
        for s in socks:
            s.close()
        hub.close()


# ------------------------------------------- elastic admission + auth

def test_elastic_admission_beyond_seed_fleet():
    """With ``max_workers`` above the seed, auto JOINs keep receiving
    fresh ids past ``num_workers`` — and every WELCOME names the
    *ceiling* as the shard space, so data sharding is identical for
    the host that joined first and the host admitted last."""
    hub = HostTransport(4, host="127.0.0.1", port=0, num_workers=2,
                        max_workers=4, welcome_config={})
    addr = tuple(hub.address)
    socks = []
    try:
        for expect in range(4):
            s, cfg = negotiate_join(addr)
            socks.append(s)
            assert (cfg["worker_id"], cfg["generation"]) == (expect, 0)
            # the shard space is the admission ceiling, not the seed
            assert cfg["num_workers"] == 4
        with pytest.raises(WireProtocolError, match="full"):
            negotiate_join(addr, connect_timeout=0.5)
    finally:
        for s in socks:
            s.close()
        hub.close()


def test_auto_join_blocked_by_grace_window_then_relessed():
    """An auto JOIN must not be handed a recently-departed worker id —
    its previous holder may be mid-reconnect — until the re-lease grace
    window expires; after expiry the id is re-leased with a bumped
    generation (fencing out the departed holder's stale frames)."""
    hub = HostTransport(4, host="127.0.0.1", port=0, num_workers=1,
                        welcome_config={}, lease_grace_s=0.5)
    addr = tuple(hub.address)
    try:
        s0, cfg0 = negotiate_join(addr)
        assert (cfg0["worker_id"], cfg0["generation"]) == (0, 0)
        s0.close()
        _poll(lambda: 0 in hub._departed, what="departure recorded")
        # inside the window: the only free id is grace-protected
        with pytest.raises(WireProtocolError, match="grace"):
            negotiate_join(addr, connect_timeout=0.3)
        # the BUSY rejection is retried past expiry: same id, new
        # generation — never a brand-new shard
        s1, cfg1 = negotiate_join(addr, connect_timeout=10.0)
        assert (cfg1["worker_id"], cfg1["generation"]) == (0, 1)
        s1.close()
    finally:
        hub.close()


def test_join_secret_challenge_and_rejections():
    """Authenticated JOIN, all four corners: a secretless joiner gets a
    readable client-side error, a wrong secret gets the leader's
    readable REJECT without ever taking a lease or a barrier seat, the
    right secret is admitted (generation 0 — the failures consumed
    nothing), and a direct HELLO cannot sidestep the challenge."""
    hub = HostTransport(4, host="127.0.0.1", port=0, num_workers=2,
                        welcome_config={"spec": {"arch": "mlp"}},
                        join_secret="open-sesame")
    addr = tuple(hub.address)
    try:
        with pytest.raises(WireProtocolError, match="authenticated"):
            negotiate_join(addr, connect_timeout=5.0)
        with pytest.raises(WireProtocolError,
                           match="authentication failed"):
            negotiate_join(addr, secret="wrong", connect_timeout=5.0)
        assert hub.live_workers() == set()      # never entered the barrier
        s, cfg = negotiate_join(addr, secret="open-sesame")
        try:
            # generation 0: the rejected attempts held no lease
            assert (cfg["worker_id"], cfg["generation"]) == (0, 0)
            assert cfg["spec"] == {"arch": "mlp"}
        finally:
            s.close()
        # a bare HELLO is not a way around the challenge
        stray = SocketWorkerClient(addr, 1, generation=0, family="tcp")
        assert stray.closed.wait(5.0)
        assert "authenticated JOIN" in (stray.reject_reason or "")
        stray.close()
        assert 1 not in hub.live_workers()
    finally:
        hub.close()


# ---------------------------------------------------------- end to end

def _host_spec(**kw):
    base = dict(arch="mlp", backend="cluster", mode="sync",
                schedule=None, cluster_workers=2, wall_budget_s=30.0,
                wall_sample_every_s=10.0, batch=16, smoke=True,
                max_gradients=12)
    base.update(kw)
    return ExperimentSpec(**base)


def _check_conservation(res):
    a = res.extra["accounting"]
    assert a["computed"] == (a["applied"] + a["dropped"] + a["buffered"]
                             + a["pending_round"] + a["in_flight"]), a
    assert res.num_gradients == a["applied"]
    return a


def test_two_host_groups_bitwise_identical_to_inproc():
    """The acceptance scenario: the same sync spec under a gradient
    budget, run once with in-process threads and once as a leader plus
    TWO separately-launched `repro join` process groups (each rebuilds
    the workload from spec JSON fetched over TCP).  Final parameters
    must be bitwise identical — the pinned ``<f4`` wire format, leased
    worker-id shards, and worker-id-ordered sync rounds leave no other
    outcome.

    Two read-only serve clients subscribe to the host run while it
    trains: they must receive pushes, never claim a barrier seat, and
    — the serving-plane acceptance bar — leave the training outcome
    bitwise untouched."""
    from repro.serve.client import ServeClient
    finals = {}
    trainer = ClusterTrainer()
    res = trainer.run(_host_spec(transport="inproc"))
    a = _check_conservation(res)
    assert a["applied"] == 12 and res.num_updates == 6
    finals["inproc"] = trainer.last_params

    spec = _host_spec(transport="host", listen="127.0.0.1:0")
    trainer2 = ClusterTrainer()
    runtime = trainer2.build_runtime(spec)
    assert runtime.listen_address[1] != 0       # resolved, advertisable
    runtime.join_platform = CHILD_PLATFORM or jax.default_backend()
    procs = [spawn_join_process(runtime.listen_address, workers=1,
                                platform=CHILD_PLATFORM)
             for _ in range(2)]
    serve_clients = [ServeClient(runtime.listen_address)
                     for _ in range(2)]
    try:
        res_h = trainer2.finish(runtime, spec)
    finally:
        codes = []
        for p in procs:
            try:
                codes.append(p.wait(timeout=60))
            except Exception:
                p.kill()
                codes.append("killed")
        for c in serve_clients:
            c.close()
    assert codes == [0, 0], codes
    a = _check_conservation(res_h)
    assert a["applied"] == 12 and res_h.num_updates == 6
    finals["host"] = trainer2.last_params
    # the platform the leader launched its join groups on is recorded
    assert res_h.extra["placement"]["worker_platforms"] == {
        "0": runtime.join_platform, "1": runtime.join_platform}

    # the serving plane saw the run but never entered it
    serving = res_h.extra["serving"]
    assert serving["clients"] == 2, serving
    for c in serve_clients:
        seen = list(c.versions_seen)
        assert seen and seen == sorted(seen), seen

    # resolved address is exposed on the result
    assert res_h.extra["listen"].startswith("127.0.0.1:")
    listening = [e for e in res_h.extra["events"]
                 if e["event"] == "listening"]
    assert listening and listening[0]["expected_workers"] == 2

    for key in finals["inproc"]:
        assert np.array_equal(np.asarray(finals["inproc"][key]),
                              np.asarray(finals["host"][key])), key


def test_elastic_e2e_admit_kill_release_and_exact_ledger():
    """The elasticity acceptance scenario, end to end over TCP: a
    2-worker run admits a third joiner mid-run (the fleet grows beyond
    the seed), survives a SIGKILLed worker whose shard is then
    re-leased to a fresh process at a bumped generation, and still
    finishes with an exact conservation ledger."""
    spec = _host_spec(transport="host", listen="127.0.0.1:0",
                      mode="async", cluster_workers=2, max_workers=3,
                      max_gradients=None, wall_budget_s=120.0)
    trainer = ClusterTrainer()
    runtime = trainer.build_runtime(spec)
    addr = runtime.listen_address

    def _applied():
        server = getattr(runtime, "server", None)
        return server.applied if server is not None else 0

    box = {}
    th = threading.Thread(
        target=lambda: box.update(res=trainer.finish(runtime, spec)),
        daemon=True)
    j0 = spawn_join_process(addr, worker_id=0, platform=CHILD_PLATFORM)
    j1 = spawn_join_process(addr, worker_id=1, platform=CHILD_PLATFORM)
    th.start()
    j2 = j3 = None
    try:
        _poll(lambda: runtime.transport.live_workers() >= {0, 1},
              timeout_s=180.0, what="seed fleet assembled")
        _poll(lambda: _applied() > 0, timeout_s=60.0,
              what="seed fleet training")

        # online admission: a third host joins the live run
        j2 = spawn_join_process(addr, platform=CHILD_PLATFORM)
        _poll(lambda: 2 in runtime.transport.live_workers(),
              timeout_s=180.0, what="worker 2 admitted mid-run")
        # the hub admits the HELLO a beat before the runtime's
        # ready-callback grows the fleet — poll, don't assert
        _poll(lambda: runtime.fleet_size == 3, timeout_s=30.0,
              what="fleet grew to 3")
        mark = _applied()
        _poll(lambda: _applied() > mark, timeout_s=60.0,
              what="grown fleet training")

        # departure: SIGKILL a seed worker (no goodbye, no flush)...
        j1.kill()
        _poll(lambda: 1 not in runtime.transport.live_workers(),
              timeout_s=60.0, what="killed worker reaped")
        # ...and re-lease its shard to a fresh process (the explicit id
        # skips the grace window; the generation bump fences the ghost)
        j3 = spawn_join_process(addr, worker_id=1,
                                platform=CHILD_PLATFORM)
        _poll(lambda: 1 in runtime.transport.live_workers(),
              timeout_s=180.0, what="shard re-leased")
        mark = _applied()
        _poll(lambda: _applied() > mark, timeout_s=60.0,
              what="re-leased fleet training")
        runtime.server.done.set()           # end the run
        th.join(120.0)
        assert not th.is_alive(), "runtime never finished"
    finally:
        codes = {}
        for name, p in (("j0", j0), ("j2", j2), ("j3", j3)):
            if p is None:
                continue
            try:
                codes[name] = p.wait(timeout=60)
            except Exception:
                p.kill()
                codes[name] = "stranded"
        if j1.poll() is None:
            j1.kill()
        j1.wait(timeout=30)
    assert codes == {"j0": 0, "j2": 0, "j3": 0}, codes
    assert j1.returncode == -9              # SIGKILL, by design

    res = box["res"]
    a = _check_conservation(res)
    assert a["applied"] > 0
    # the per-worker ledger covers every member that ever existed —
    # including the one admitted beyond the seed fleet
    assert set(a["computed_per_worker"]) == {"0", "1", "2"}

    events = res.extra["events"]
    grow = [e for e in events if e["event"] == "fleet_grow"]
    assert grow and grow[0]["to_workers"] == 3, grow
    joins = [e for e in events if e["event"] == "member_join"]
    assert any(e["worker"] == 2 for e in joins), joins
    # the re-leased shard came back under a bumped generation
    assert any(e["worker"] == 1 and e["generation"] >= 1
               for e in joins), joins
    assert any(e["event"] == "member_gone" and e["worker"] == 1
               for e in events)


def test_kill_the_leader_joined_worker_exits_cleanly():
    """When the leader dies, a joined worker must see EOF and exit 0 —
    not hang in ``recv`` or strand in the send retry loop."""
    from repro.api.trainers import SIM_WORKLOADS
    from repro.core.slab import slab_codec

    spec = _host_spec(mode="async", cluster_workers=1,
                      max_gradients=None)
    hub = HostTransport(8, host="127.0.0.1", port=0, num_workers=1,
                        welcome_config={"spec": spec.to_dict()})
    proc = spawn_join_process(hub.address, workers=1,
                              platform=CHILD_PLATFORM)
    try:
        assert hub.wait_for_workers(1, timeout=180.0), \
            "joined worker never connected"
        # put the worker mid-training-loop: publish real params so it
        # is actively fetching, computing, and sending when the leader
        # vanishes
        _, init_params, _, _ = SIM_WORKLOADS[spec.arch](spec)
        slab = np.asarray(slab_codec(init_params).encode(init_params))
        hub.publish_params(ParamsMsg(0, slab))
        _poll(lambda: hub.pending_gradients() > 0
              or sum(hub.received_counts().values()) > 0,
              timeout_s=60.0, what="worker training")
        hub.close()                             # the leader dies
        assert proc.wait(timeout=30) == 0       # EOF -> clean exit
    finally:
        if proc.poll() is None:
            proc.kill()
        hub.close()
