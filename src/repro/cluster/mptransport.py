"""Multi-process slab transport: sockets + one-process-per-worker.

:class:`SocketTransport` implements the :class:`~repro.cluster.
transport.Transport` protocol over real sockets (TCP or Unix-domain):
the server side is a *hub* — a listener plus one reader/writer thread
pair per accepted worker connection — and the worker side is a
:class:`SocketWorkerClient` endpoint created by :meth:`SocketTransport.
connect` (same process) or by connecting to ``hub.address`` from
another process.  :class:`ProcTransport` extends the hub with a
``multiprocessing`` launcher that runs each worker in its own OS
process with its own JAX runtime, so GIL contention, stale parameter
reads, stragglers, and SIGKILL worker death are physical across address
spaces.

**Wire format** — the slab layout (:mod:`repro.core.slab`) is the
schema on both ends, so every message is ONE length-prefixed frame with
no per-leaf serialization.  The format is **versioned and pinned**::

    frame   := header payload
    header  := !BI            (type: u8, payload length: u32)
    HELLO   := !IHIi          magic, proto, worker_id, generation
    HELLO'  := !IHIiB         ... + slab dtype code (non-f32 peers only)
    JOIN    := !IHi           magic, proto, requested worker id (-1=auto)
    WELCOME := !IH json       magic, proto, lease + spec JSON (hub ->)
    REJECT  := !IH utf-8      magic, proto, readable reason   (hub ->)
    GRAD    := !IiQ raw-slab  worker_id, version, seq
    PARAMS  := !ii  raw-slab  version, restore-epoch          (hub ->)
    SERVE   := !IH            magic, proto — read-only subscribe
    PING    := !IH            magic, proto — leader liveness  (hub ->)
    PONG    := !IH            magic, proto — liveness reply
    STATS   := !IH [json]     magic, proto — read-only stats subscribe
                              (client ->, empty body); stats payload
                              push (hub ->, JSON body)
    CHALLENGE := !IH nonce    magic, proto, 32-byte nonce    (hub ->)
    AUTH    := !IH digest     magic, proto, HMAC-SHA256(secret, nonce)

``raw-slab`` is the ``(P_pad,)`` slab as **little-endian ``<f4``** —
pinned on both encode and decode (a big-endian host byteswaps at the
boundary, a little-endian host pays nothing), so f32 payloads
round-trip bitwise across any pair of hosts, which is what makes the
cross-process and cross-host parity tests exact.

**Negotiated slab dtype** — a peer whose run declares ``slab_dtype``
other than f32 (``ExperimentSpec.slab_dtype="bf16"``) says so with ONE
trailing byte on its HELLO (``HELLO'`` above, dtype code 0=f32,
1=bf16); its GRAD/PARAMS payloads then carry the slab as little-endian
raw bf16 (``<u2`` bit patterns), halving every slab frame on the wire
(``wire.tx_bytes``/``rx_bytes``).  The negotiation is strictly
additive: an f32 peer sends the original 14-byte HELLO — byte for byte
the pinned v1 frame — and old hubs reject an extended HELLO readably
(length check), so mixed builds fail fast instead of misparsing slabs.
The hub tracks the dtype per connection, validates GRAD frame lengths
against the connection's element size, and caches one encoded PARAMS
frame per dtype per published version (the broadcast stays
swap-a-pointer cheap).  Read-only SERVE subscribers inherit the run's
dtype (it rides the WELCOME spec).  The first frame on
every accepted connection must be a HELLO or JOIN carrying the protocol
magic and version: a stray TCP client, or a peer from an incompatible
build, is rejected with a logged, readable error (and a best-effort
REJECT frame) instead of being misparsed as a worker —
:attr:`SocketTransport.rejected_peers` counts them, and a rejected
connection never enters the fleet barrier.  Every frame length is
validated against ``_MAX_FRAME`` (and HELLO/JOIN against their exact
struct sizes) before any payload is read, so a peer that lost frame
sync cannot wedge a reader on a garbage multi-gigabyte length.
JOIN/WELCOME implement the multi-host leader handshake — worker-id
leases with generation fencing — in :mod:`repro.cluster.hostlink`.

**Channel semantics** match :class:`~repro.cluster.transport.
InProcTransport` exactly (the conformance suite in
``tests/test_transport.py`` runs against all three):

  * gradients: per-connection FIFO into one bounded hub queue.  A full
    queue blocks the connection's reader, TCP/UDS flow control
    propagates the stall to the worker's socket, and the worker's small
    outbound queue fills — ``send_gradient`` returning ``False`` is
    end-to-end physical backpressure;
  * params: versioned broadcast.  The hub keeps the latest published
    frame; per-connection writers push it, *coalescing* intermediate
    versions for slow readers (only the newest publication matters —
    including a checkpoint restore that moves the version backwards).

**Shutdown / accounting**: a SIGKILLed worker can die mid-frame; the
hub discards the torn tail frame (``torn_frames``) and counts only
complete frames in :meth:`received_counts` — which is therefore the
exact "computed" side of the conservation ledger on both socket
transports (whatever never reached the hub died with the sender,
exactly like a thread worker killed before ``send``).  ``quiesce()``
joins the connection readers after the producers are gone, making
``pending_gradients()`` exact for the final drain.

**Membership / barrier**: the runtime registers a worker with the
server when its HELLO arrives (:attr:`SocketTransport.on_worker_ready`)
and deregisters it when its connection dies
(:attr:`~SocketTransport.on_worker_gone`) — a child that is still
importing JAX must not stall a sync barrier it cannot contribute to.
``hold_params``/``release_params`` implement the fleet-ready barrier's
starting gun: until release, connected workers idle in
``fetch_params`` instead of banking gradients before the clock starts.

**Serving plane**: a peer whose first frame is SERVE (instead of
HELLO/JOIN) becomes a *read-only* subscriber to the params broadcast.
Serve connections never claim a ``worker_id``, so every membership
surface — the fleet barrier, ``live_workers``, ``received_counts`` and
with it the conservation ledger — excludes them for free, and a SERVE
peer that tries to send a GRAD frame is rejected like any
unidentified sender.  The publish path is already slow-reader-safe for
them: ``publish_params`` only swaps a frame pointer under a lock
(never writes a socket), each connection has its own writer thread,
and coalescing means a stalled reader costs the hub exactly one wedged
writer — never a torn or delayed flush.  ``serve_every`` down-samples
the push stream per serve connection (every Nth version), trading
client-visible staleness for broadcast bandwidth; ``serve_stats``
reports per-client push/version/skip counters.

**Stats plane**: a peer whose first frame is STATS becomes a read-only
subscriber to the hub's *telemetry* push (``python -m repro top``):
small JSON payloads — ledger counters, staleness percentiles, queue
depth — on a fixed cadence, produced by :attr:`SocketTransport.
stats_provider`.  Like serve peers, stats connections never hold a
``worker_id``, never enter the barrier or the conservation ledger, and
``quiesce`` skips them; unlike serve peers they are *not* sent the
params broadcast at all (a stats reader costs the run a few hundred
bytes of JSON per tick, never a slab) — which is why a sync run stays
bitwise-identical with a stats reader attached (regression-tested).
The hub keeps a small ring of recent cells (fed by the cadence thread,
subscribers or not); a newly-admitted stats reader is sent the ring as
one ``{"history": [...]}`` backfill frame before live pushes begin, so
a late-attaching ``repro top`` starts with rates instead of starting
blind — while the live pushes themselves stay coalesced latest-only.
Old peers ignore unknown frame types, so STATS rides protocol v1
without a version bump.

**Join authentication**: a hub constructed with a shared join secret
(the multi-host leader's ``--join-secret``) answers JOIN with a
CHALLENGE frame carrying a fresh random nonce instead of a WELCOME.
The peer proves possession of the secret by replying AUTH with
``HMAC-SHA256(secret, nonce)``; a correct digest completes the pending
lease (WELCOME), a wrong one is rejected readably, and a peer that
HELLOs directly — skipping the challenge — is rejected too.  Old peers
ignore unknown frame types, so CHALLENGE/AUTH ride protocol v1 exactly
like STATS did.  Read-only SERVE/STATS subscribers are deliberately
*not* challenged: they can observe, never contribute.

**Liveness**: with ``heartbeat_s > 0`` the hub PINGs every
authenticated connection on that cadence (never a silent stray — the
model-withholding rule extends to control frames).  Clients reply PONG
(ignored beyond updating receive timestamps) and treat *any* frame as
proof of life, so a worker or serve client can distinguish a hung
leader — process alive, event loop wedged — from a merely quiet one,
and exit with a readable error instead of waiting forever.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import hmac
import json
import logging
import os
import queue
import socket
import struct
import sys
import tempfile
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Set, Tuple

import ml_dtypes
import numpy as np

from repro.cluster.transport import GradientMsg, ParamsMsg
from repro.obs.telemetry import NULL

_log = logging.getLogger("repro.cluster.transport")

# protocol identity: the first frame of every connection must carry both
# (HELLO or JOIN), or the peer is rejected before it can touch the fleet
_MAGIC = 0x534C4142                  # "SLAB"
_PROTO_VERSION = 1

_HDR = struct.Struct("!BI")          # frame type, payload length
_HELLO = struct.Struct("!IHIi")      # magic, proto, worker_id, generation
_HELLO_DT = struct.Struct("!IHIiB")  # ... + slab dtype code (non-f32 only)
_JOIN = struct.Struct("!IHi")        # magic, proto, requested id (-1=auto)
_CTRL = struct.Struct("!IH")         # magic, proto (WELCOME/REJECT prefix)
_GRAD = struct.Struct("!IiQ")        # worker_id, version, seq
_PARAMS = struct.Struct("!ii")       # version, restore epoch

_F_HELLO, _F_GRAD, _F_PARAMS, _F_JOIN, _F_WELCOME, _F_REJECT = \
    1, 2, 3, 4, 5, 6
_F_SERVE, _F_PING, _F_PONG = 7, 8, 9
_F_STATS = 10
_F_CHALLENGE, _F_AUTH = 11, 12

# HMAC-SHA256 over the challenge nonce: both sides fixed-size
_AUTH_NONCE_LEN = 32
_AUTH_DIGEST_LEN = 32

# leader-side ring of recent stats cells: enough for a late-attaching
# `repro top` to backfill rates (~2 minutes at the default 0.5s cadence)
_STATS_HISTORY_LEN = 240

# one frame must fit in memory several times over; anything bigger is a
# corrupted header (e.g. a reader that lost frame sync), not a real slab
_MAX_FRAME = 1 << 30

# the pinned slab byte order: little-endian on the wire, always.  On a
# little-endian host (every CI/dev machine) this is the native layout
# and costs nothing; a big-endian host byteswaps at the boundary.  f32
# is the default (and the only layout protocol v1 ever shipped); bf16
# is negotiated per connection via the extended HELLO and travels as
# raw little-endian bf16 bit patterns (<u2 on the wire, viewed back as
# ml_dtypes.bfloat16 — numpy has no native bf16 — at the boundary)
_SLAB_DTYPE = np.dtype("<f4")
_BF16 = np.dtype(ml_dtypes.bfloat16)
_DT_F32, _DT_BF16 = 0, 1             # HELLO' slab dtype codes
_DT_NAMES = {_DT_F32: "f32", _DT_BF16: "bf16"}
_DT_CODES = {name: code for code, name in _DT_NAMES.items()}
_SLAB_ITEMSIZE = {"f32": 4, "bf16": 2}


class WireProtocolError(RuntimeError):
    """A peer violated the slab wire protocol (bad magic, version
    mismatch, malformed handshake, rejected join)."""


def _recv_exact(sock: socket.socket, n: int
                ) -> "tuple[Optional[bytes], bool]":
    """Read exactly ``n`` bytes.  Returns ``(data, partial)``: data is
    ``None`` on EOF / error, and ``partial`` is True when the peer died
    after delivering *some* of the bytes — a torn read, as opposed to a
    clean EOF on a frame boundary.  (Mattering for accounting: a
    SIGKILL can cut a frame mid-header, not just mid-payload.)"""
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        try:
            k = sock.recv_into(view[got:], n - got)
        except (OSError, ValueError):
            return None, got > 0
        if k == 0:
            return None, got > 0
        got += k
    return bytes(buf), False


def _slab_to_bytes(arr, dtype_name: str = "f32") -> bytes:
    """The slab's wire image: contiguous little-endian bytes — the
    pinned byte order, regardless of the producing host's own.  f32
    travels as ``<f4``; a bf16 connection ships the raw bf16 bit
    patterns (``<u2``), half the bytes per element."""
    if dtype_name == "bf16":
        a = np.ascontiguousarray(np.asarray(arr))
        if a.dtype != _BF16:
            a = a.astype(_BF16)
        return a.view(np.uint16).astype("<u2", copy=False).tobytes()
    a = np.ascontiguousarray(np.asarray(arr, dtype=np.float32))
    return a.astype(_SLAB_DTYPE, copy=False).tobytes()


def _slab_from_payload(payload: bytes, offset: int,
                       dtype_name: str = "f32") -> np.ndarray:
    """Decode a wire slab: explicit little-endian, normalized to the
    native byte order so downstream jnp/staging code never sees a
    swapped view.  bf16 payloads come back as ``ml_dtypes.bfloat16``
    arrays (jnp adopts them as ``jnp.bfloat16`` with no conversion)."""
    if dtype_name == "bf16":
        u = np.frombuffer(payload, np.dtype("<u2"), offset=offset)
        if u.dtype != np.uint16:        # big-endian host: byteswap once
            u = u.astype(np.uint16)
        return u.view(_BF16)
    slab = np.frombuffer(payload, _SLAB_DTYPE, offset=offset)
    if slab.dtype != np.float32:        # big-endian host: byteswap once
        slab = slab.astype(np.float32)
    return slab


def _grad_frame(msg: GradientMsg, dtype_name: str = "f32") -> bytes:
    slab = _slab_to_bytes(msg.grad, dtype_name)
    return (_HDR.pack(_F_GRAD, _GRAD.size + len(slab))
            + _GRAD.pack(msg.worker_id, msg.version, msg.seq) + slab)


def _params_frame(msg: ParamsMsg, dtype_name: str = "f32") -> bytes:
    slab = _slab_to_bytes(msg.params, dtype_name)
    return (_HDR.pack(_F_PARAMS, _PARAMS.size + len(slab))
            + _PARAMS.pack(msg.version, msg.epoch) + slab)


def _hello_frame(worker_id: int, generation: int,
                 slab_dtype: str = "f32") -> bytes:
    """An f32 peer sends the original 14-byte HELLO — bit for bit the
    pinned v1 frame; only a non-f32 peer appends the dtype byte."""
    if slab_dtype == "f32":
        return (_HDR.pack(_F_HELLO, _HELLO.size)
                + _HELLO.pack(_MAGIC, _PROTO_VERSION, worker_id,
                              generation))
    return (_HDR.pack(_F_HELLO, _HELLO_DT.size)
            + _HELLO_DT.pack(_MAGIC, _PROTO_VERSION, worker_id,
                             generation, _DT_CODES[slab_dtype]))


def _join_frame(requested_id: int) -> bytes:
    return (_HDR.pack(_F_JOIN, _JOIN.size)
            + _JOIN.pack(_MAGIC, _PROTO_VERSION, requested_id))


def _ctrl_frame(ftype: int, body: bytes) -> bytes:
    return (_HDR.pack(ftype, _CTRL.size + len(body))
            + _CTRL.pack(_MAGIC, _PROTO_VERSION) + body)


def _welcome_frame(cfg: Dict[str, Any]) -> bytes:
    return _ctrl_frame(_F_WELCOME, json.dumps(cfg).encode("utf-8"))


def _reject_frame(reason: str) -> bytes:
    return _ctrl_frame(_F_REJECT, reason.encode("utf-8"))


def _serve_frame() -> bytes:
    """Read-only subscribe request (client -> hub, first frame)."""
    return _ctrl_frame(_F_SERVE, b"")


def _stats_frame(payload: bytes = b"") -> bytes:
    """Empty body: a read-only stats subscribe request (client ->,
    first frame).  JSON body: one stats payload push (hub ->)."""
    return _ctrl_frame(_F_STATS, payload)


def _challenge_frame(nonce: bytes) -> bytes:
    """Authenticated-JOIN challenge (hub ->): prove you hold the shared
    join secret before the lease is granted."""
    return _ctrl_frame(_F_CHALLENGE, nonce)


def _auth_frame(digest: bytes) -> bytes:
    """Challenge response (client ->): HMAC-SHA256(secret, nonce)."""
    return _ctrl_frame(_F_AUTH, digest)


def _auth_digest(secret: str, nonce: bytes) -> bytes:
    return hmac.new(secret.encode("utf-8"), nonce,
                    hashlib.sha256).digest()


def _ping_frame() -> bytes:
    return _ctrl_frame(_F_PING, b"")


def _pong_frame() -> bytes:
    return _ctrl_frame(_F_PONG, b"")


def _peer_error(magic: int, proto: int) -> Optional[str]:
    """Reject reason for a bad protocol identity, or None when valid."""
    if magic != _MAGIC:
        return (f"bad magic 0x{magic:08X} (expected 0x{_MAGIC:08X}) — "
                "peer is not a repro slab endpoint")
    if proto != _PROTO_VERSION:
        return (f"protocol version mismatch: peer speaks v{proto}, this "
                f"hub speaks v{_PROTO_VERSION}")
    return None


def _configure(sock: socket.socket) -> None:
    if sock.family == socket.AF_INET:
        # grad/params frames are latency-critical; never Nagle-delay them
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)


# ======================================================== server side


class _Conn:
    """One accepted worker connection: a reader thread (gradients in)
    and a writer thread (coalesced params broadcast out)."""

    def __init__(self, hub: "SocketTransport", sock: socket.socket):
        self.hub = hub
        self.sock = sock
        self.worker_id: Optional[int] = None
        self.generation = 0
        # the negotiated slab dtype for THIS connection: f32 unless the
        # peer's HELLO carried a dtype byte (serve conns inherit the
        # run's dtype at admission).  Controls GRAD decode, GRAD length
        # validation, and which encoded PARAMS frame the writer pushes
        self.slab_dtype = "f32"
        self.authenticated = False          # valid HELLO/JOIN/SERVE seen
        self.leased_wid: Optional[int] = None   # set by a JOIN lease
        # authenticated-JOIN state (hubs with a join secret): a JOIN is
        # parked as pending_join while the CHALLENGE round-trips; the
        # lease is only granted once the AUTH digest verifies
        self.awaiting_auth = False          # CHALLENGE sent, AUTH due
        self.auth_ok = False                # digest verified
        self.auth_nonce: Optional[bytes] = None
        self.pending_join: Optional[int] = None
        # serving plane: read-only params subscribers.  worker_id stays
        # None for them, which is what keeps every membership surface
        # (barrier, ledger, live_workers) worker-only with no new code
        self.is_serve = False
        self.serve_id: Optional[int] = None
        # stats plane: read-only telemetry subscribers (repro top).
        # Same worker_id=None trick as serve peers, and additionally
        # excluded from the params broadcast entirely
        self.is_stats = False
        self.stats_id: Optional[int] = None
        self.pushes = 0                     # params frames shipped
        self.last_pushed_version: Optional[int] = None
        self.skipped_pushes = 0             # down-sampled by serve_every
        self.closed = threading.Event()
        self._params_ev = threading.Event()
        self._last_sent: Optional[bytes] = None
        self._lock = threading.Lock()       # close() idempotence
        self._wlock = threading.Lock()      # whole frames only: the
        #                                     writer thread and control
        #                                     replies share one socket
        _configure(sock)
        self.reader = threading.Thread(target=self._read_loop,
                                       name="hub-reader", daemon=True)
        self.writer = threading.Thread(target=self._write_loop,
                                       name="hub-writer", daemon=True)
        self._params_ev.set()               # push current params on join
        self.reader.start()
        self.writer.start()

    # ------------------------------------------------------- gradients in
    def _frame_error(self, ftype: int, n: int) -> Optional[str]:
        """Header-level validation, BEFORE the payload is read — a
        garbage header must never commit the reader to a garbage-sized
        read."""
        if ftype == _F_HELLO:
            if self.worker_id is not None:
                return ("repeated HELLO on one connection — a peer "
                        "identifies itself exactly once (a re-HELLO "
                        "under another id would ghost-register the "
                        "first one in the sync barrier)")
            return None if n in (_HELLO.size, _HELLO_DT.size) else \
                (f"HELLO frame has length {n}, expected {_HELLO.size} " \
                 f"or {_HELLO_DT.size}")
        if ftype == _F_JOIN:
            if self.authenticated:
                return ("JOIN on an already-authenticated connection — "
                        "one connection holds at most one lease")
            return None if n == _JOIN.size else \
                f"JOIN frame has length {n}, expected {_JOIN.size}"
        if ftype == _F_SERVE:
            if self.authenticated:
                return ("SERVE on an already-authenticated connection "
                        "— a trainer cannot demote itself to a reader "
                        "mid-stream")
            return None if n == _CTRL.size else \
                f"SERVE frame has length {n}, expected {_CTRL.size}"
        if ftype == _F_STATS:
            if self.authenticated:
                return ("STATS on an already-authenticated connection "
                        "— a trainer cannot demote itself to a stats "
                        "reader mid-stream")
            return None if n == _CTRL.size else \
                f"STATS subscribe frame has length {n}, expected " \
                f"{_CTRL.size}"
        if ftype == _F_AUTH:
            if self.authenticated:
                return ("AUTH on an already-authenticated connection — "
                        "the challenge round-trips exactly once")
            if not self.awaiting_auth:
                return ("unexpected AUTH frame — this connection has "
                        "no challenge outstanding")
            return None if n == _CTRL.size + _AUTH_DIGEST_LEN else \
                f"AUTH frame has length {n}, expected " \
                f"{_CTRL.size + _AUTH_DIGEST_LEN}"
        if not self.authenticated:
            return (f"first frame has type {ftype}, not "
                    "HELLO/JOIN/SERVE/STATS — peer is not speaking the "
                    "repro slab protocol")
        if n > _MAX_FRAME:
            return (f"frame length {n} exceeds the {_MAX_FRAME}-byte "
                    "maximum — peer lost frame sync")
        if ftype == _F_GRAD and (n < _GRAD.size or
                                 (n - _GRAD.size)
                                 % _SLAB_ITEMSIZE[self.slab_dtype]):
            return (f"malformed GRAD frame: payload length {n} is not "
                    f"header + whole {self.slab_dtype} slab elements — "
                    "peer lost frame sync")
        return None

    def _read_loop(self) -> None:
        try:
            while not self.closed.is_set():
                hdr, partial = _recv_exact(self.sock, _HDR.size)
                if hdr is None:
                    if partial:
                        self.hub._note_torn()   # died mid-header
                    break                       # else: clean EOF
                ftype, n = _HDR.unpack(hdr)
                err = self._frame_error(ftype, n)
                if err is not None:
                    self.hub._reject(self, err)
                    break
                payload, _ = _recv_exact(self.sock, n)
                if payload is None:
                    self.hub._note_torn()       # died mid-frame: discard
                    break
                self.hub.obs.count("wire.rx_bytes", _HDR.size + n)
                if ftype == _F_HELLO:
                    if n == _HELLO_DT.size:
                        magic, proto, wid, gen, dtc = \
                            _HELLO_DT.unpack(payload)
                    else:
                        magic, proto, wid, gen = _HELLO.unpack(payload)
                        dtc = _DT_F32   # bare v1 HELLO: pinned f32
                    err = _peer_error(magic, proto)
                    if err is None and dtc not in _DT_NAMES:
                        err = (f"unknown slab dtype code {dtc} in "
                               "HELLO — peer is from a newer build "
                               "negotiating a dtype this hub does not "
                               "speak")
                    if err is None:
                        # before admission: the first params push must
                        # already use the negotiated encoding
                        self.slab_dtype = _DT_NAMES[dtc]
                    # _admit_hello claims conn.worker_id inside the
                    # hub's admission lock — concurrent admissions for
                    # one id must see each other (duplicate fencing)
                    err = err \
                        or self.hub._admit_hello(self, wid, gen)
                    if err is not None:
                        self.hub._reject(self, err)
                        break
                    self.authenticated = True
                    self.hub._on_hello(self)
                elif ftype == _F_JOIN:
                    magic, proto, req = _JOIN.unpack(payload)
                    err = _peer_error(magic, proto) \
                        or self.hub._on_join(self, req)
                    if err is not None:
                        self.hub._reject(self, err)
                        break
                    # a secret-bearing hub parks the JOIN behind a
                    # CHALLENGE: the connection stays unauthenticated
                    # (no params broadcast, no lease) until AUTH lands
                    self.authenticated = not self.awaiting_auth
                elif ftype == _F_AUTH:
                    magic, proto = _CTRL.unpack(payload[:_CTRL.size])
                    err = _peer_error(magic, proto) \
                        or self.hub._on_auth(self,
                                             payload[_CTRL.size:])
                    if err is not None:
                        self.hub._reject(self, err)
                        break
                    self.authenticated = True
                elif ftype == _F_SERVE:
                    magic, proto = _CTRL.unpack(payload)
                    err = _peer_error(magic, proto) \
                        or self.hub._on_serve(self)
                    if err is not None:
                        self.hub._reject(self, err)
                        break
                    self.authenticated = True
                    self.hub._on_serve_ready(self)
                elif ftype == _F_STATS:
                    magic, proto = _CTRL.unpack(payload[:_CTRL.size])
                    err = _peer_error(magic, proto) \
                        or self.hub._on_stats(self)
                    if err is not None:
                        self.hub._reject(self, err)
                        break
                    self.authenticated = True
                    self.hub._on_stats_ready(self)
                elif ftype == _F_PONG:
                    pass                    # liveness reply; receipt
                    #                         alone is the signal
                elif ftype == _F_GRAD:
                    if self.worker_id is None:
                        reason = "GRAD frame before HELLO — the peer " \
                                 "never identified itself"
                        if self.is_serve:
                            reason = ("GRAD frame from a read-only "
                                      "serve client")
                        elif self.is_stats:
                            reason = ("GRAD frame from a read-only "
                                      "stats client")
                        self.hub._reject(self, reason)
                        break
                    wid, version, seq = _GRAD.unpack(
                        payload[:_GRAD.size])
                    grad = _slab_from_payload(payload, _GRAD.size,
                                              self.slab_dtype)
                    msg = GradientMsg(wid, grad, version, seq)
                    # the span brackets the bounded put: its duration IS
                    # the backpressure wait when the hub queue is full
                    with self.hub.obs.span(f"worker/{wid}/wire",
                                           "grad_rx", version=version,
                                           seq=seq,
                                           bytes=_HDR.size + n):
                        ok = self.hub._enqueue(msg)
                    if ok:                      # blocks: backpressure
                        self.hub._count_received(wid)
                # other frame types are ignored (forward compat)
        finally:
            self.close()
            self.hub._conn_closed(self)

    # ----------------------------------------------------- params out
    def notify_params(self) -> None:
        self._params_ev.set()

    def send_frame(self, frame: bytes,
                   lock_timeout: Optional[float] = None) -> bool:
        """Write one whole frame (serialized against the params writer
        thread).  False when the connection is gone — or, with
        ``lock_timeout``, when the write lock stayed contended that
        long (a writer wedged in ``sendall`` against a stalled peer
        must not be able to wedge the *reader* too)."""
        if lock_timeout is None:
            acquired = self._wlock.acquire()
        else:
            acquired = self._wlock.acquire(timeout=lock_timeout)
        if not acquired:
            return False
        try:
            self.sock.sendall(frame)
            self.hub.obs.count("wire.tx_bytes", len(frame))
            return True
        except OSError:
            return False
        finally:
            self._wlock.release()

    def _write_loop(self) -> None:
        while not self.closed.is_set():
            if not self._params_ev.wait(0.2):
                continue
            self._params_ev.clear()
            # latest only (coalesced), in this connection's negotiated
            # dtype — same frame object per (version, dtype), so the
            # identity-based _last_sent dedup below still holds
            frame = self.hub._pub_frame_for(self.slab_dtype)
            # never broadcast parameters to a connection that has not
            # authenticated: a silent stray peer must not receive the
            # model (the HELLO handler re-arms the push on admission)
            if frame is None or frame is self._last_sent \
                    or not self.authenticated:
                continue
            if self.is_stats:
                # stats readers are never sent the params broadcast —
                # a few hundred bytes of JSON per tick (pushed by the
                # stats thread via send_frame), never a slab.  This is
                # what keeps a sync run bitwise-identical with a stats
                # reader attached
                self._last_sent = frame
                continue
            if self.is_serve:
                version, = _PARAMS.unpack_from(frame, _HDR.size)[:1]
                every = max(1, self.hub.serve_every)
                if every > 1 and version % every and version != 0:
                    # the staleness-vs-throughput knob: serve clients
                    # only get every Nth version (version 0 — the
                    # initial model — always ships), so a reader can
                    # run up to N-1 versions stale in exchange for
                    # 1/N of the broadcast bandwidth
                    self._last_sent = frame
                    self.skipped_pushes += 1
                    continue
            if not self.send_frame(frame):
                break
            self._last_sent = frame
            if self.is_serve:
                self.pushes += 1
                self.last_pushed_version = version

    # ------------------------------------------------------------- misc
    def half_close(self) -> None:
        """Stop the params direction (worker sees EOF and shuts down)
        while still reading its in-flight gradient frames to the end."""
        try:
            self.sock.shutdown(socket.SHUT_WR)
        except OSError:
            pass

    def close(self) -> None:
        with self._lock:
            if self.closed.is_set():
                return
            self.closed.set()
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


class SocketTransport:
    """The server-side hub: a full :class:`Transport` over real sockets.

    ``recv_gradient`` / ``publish_params`` / ``pending_gradients`` /
    ``quiesce`` are the parameter server's half and run in the hub
    process.  Workers use :class:`SocketWorkerClient` endpoints —
    :meth:`connect` builds one in-process (thread workers), and child
    processes connect to :attr:`address` themselves.  The hub's own
    ``send_gradient`` / ``fetch_params`` are local loopbacks (no
    socket), kept so the hub satisfies the whole protocol.

    ``grad_capacity`` bounds the hub gradient queue exactly like
    :class:`InProcTransport` (0 = unbounded); the bound propagates to
    workers through socket flow control (see module docstring).

    TCP mode binds ``(host, port)`` — ``port=0`` (the default) picks an
    ephemeral port, an explicit port makes the address advertisable
    ahead of time (the multi-host leader's requirement); either way the
    *resolved* address is :attr:`address`.  ``SO_REUSEADDR`` is set so a
    fast restart can rebind the same port while the previous hub's
    connections sit in TIME_WAIT.
    """

    # the telemetry bus; the runtime swaps in its live bus before the
    # run starts.  Class attribute (not per-instance state in __init__)
    # so directly-constructed hubs in tests/benchmarks get the no-op
    # bus with zero setup
    obs = NULL

    def __init__(self, grad_capacity: int = 0, *, family: str = "unix",
                 host: str = "127.0.0.1", port: int = 0,
                 heartbeat_s: float = 0.0, serve_every: int = 1,
                 slab_dtype: str = "f32"):
        assert family in ("unix", "tcp"), family
        assert slab_dtype in _DT_CODES, slab_dtype
        self.family = family
        # the RUN's declared slab dtype: what publish_params encodes
        # eagerly, what connect() hands in-process worker endpoints,
        # and what serve subscribers inherit.  Individual connections
        # may still negotiate their own via HELLO'
        self.slab_dtype = slab_dtype
        self.heartbeat_s = float(heartbeat_s)   # 0 = no PINGs
        self.serve_every = max(1, int(serve_every))
        self._sockdir: Optional[str] = None
        if family == "unix":
            self._sockdir = tempfile.mkdtemp(prefix="repro-slab-hub-")
            self.address: Any = os.path.join(self._sockdir, "hub.sock")
            lsock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            lsock.bind(self.address)
        else:
            lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            lsock.bind((host, port))
            self.address = lsock.getsockname()
        lsock.listen(128)
        lsock.settimeout(0.2)               # close() unblocks accept
        self._lsock = lsock
        self._grads: "queue.Queue[GradientMsg]" = \
            queue.Queue(maxsize=grad_capacity)
        self._closed = threading.Event()
        self._conns: List[_Conn] = []
        self._conns_cond = threading.Condition()
        self._received: Dict[int, int] = {}
        self._recv_lock = threading.Lock()
        self._torn = 0
        self._rejected = 0
        self._pub_frame: Optional[bytes] = None
        self._pub_msg: Optional[ParamsMsg] = None
        # per-dtype encodings of the CURRENT publication, keyed by
        # dtype name; reset on every publish, filled lazily for
        # dtypes other than the run's own (see _pub_frame_for)
        self._pub_frames: Dict[str, bytes] = {}
        self._pub_cond = threading.Condition()
        self._held_frame: Optional[bytes] = None
        self._hold = False          # hold_params(): see fleet barrier
        self._draining = False      # half_close_workers() was called
        # membership hooks (set by the runtime before spawning): called
        # from hub reader threads with (worker_id, generation) when a
        # worker finishes connecting / when its connection dies.  The
        # proc runtime registers workers with the server on HELLO — a
        # worker that is still importing JAX must not hold up a sync
        # barrier it cannot yet contribute to
        self.on_worker_ready: Optional[Any] = None
        self.on_worker_gone: Optional[Any] = None
        # serving-plane hook + admission counter (see _on_serve)
        self.on_serve_ready: Optional[Any] = None
        self._serve_seq = 0
        self._serve_conns: List[_Conn] = []     # every admitted, ever
        # stats plane: a zero-arg callable returning a JSON-encodable
        # dict (the runtime installs one once the server exists); the
        # push thread starts when the provider is installed (the
        # stats_provider property setter) and ticks every stats_every_s
        # even with no subscribers, feeding the history ring a
        # late-attaching `repro top` backfills from
        self.stats_every_s = 0.5
        self._stats_seq = 0
        self._stats_conns: List[_Conn] = []     # every admitted, ever
        self._stats_thread: Optional[threading.Thread] = None
        self._stats_history: Any = \
            collections.deque(maxlen=_STATS_HISTORY_LEN)
        self._stats_provider: Optional[Any] = None
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="hub-accept", daemon=True)
        self._accept_thread.start()
        self._hb_thread: Optional[threading.Thread] = None
        if self.heartbeat_s > 0:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop, name="hub-heartbeat",
                daemon=True)
            self._hb_thread.start()

    # ------------------------------------------------------- accept side
    def _accept_loop(self) -> None:
        while not self._closed.is_set():
            try:
                sock, _ = self._lsock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            with self._conns_cond:
                conn = _Conn(self, sock)
                self._conns.append(conn)
            if self._draining:
                # shutdown already began: a late joiner (e.g. a respawn
                # that was still compiling) gets its EOF immediately,
                # so it stops instead of training against a dead run
                conn.half_close()

    def _admit_hello(self, conn: _Conn, worker_id: int,
                     generation: int) -> Optional[str]:
        """Membership policy hook: a reject reason, or None to admit.
        On admit the hook MUST claim ``conn.worker_id``/``generation``
        inside its own critical section, so concurrent admissions for
        the same id observe each other.  The base hub admits every
        well-formed HELLO; the multi-host :class:`~repro.cluster.
        hostlink.HostTransport` fences stale generations and duplicate
        worker ids here."""
        with self._conns_cond:
            conn.worker_id, conn.generation = worker_id, generation
        return None

    def _on_join(self, conn: _Conn, requested_id: int) -> Optional[str]:
        """JOIN (lease negotiation) hook — only the multi-host hub
        implements it; anything else tells the peer to HELLO directly."""
        return ("this hub does not negotiate worker-id leases (not a "
                "host transport) — connect with HELLO")

    def _on_auth(self, conn: _Conn, digest: bytes) -> Optional[str]:
        """AUTH (challenge response) hook — only a hub that issued a
        CHALLENGE (a secret-bearing :class:`~repro.cluster.hostlink.
        HostTransport`) can verify one."""
        return ("unexpected AUTH frame — this hub issued no challenge")

    def _on_serve(self, conn: _Conn) -> Optional[str]:
        """SERVE (read-only subscribe) hook — only the multi-host hub
        admits serve clients; the plain hub has no spec to hand them
        and no serving story."""
        return ("this hub does not admit serve clients (not a host "
                "transport) — point `repro infer` at a training leader")

    def _on_serve_ready(self, conn: _Conn) -> None:
        """An admitted serve connection just authenticated: arm its
        params push (same re-arm as HELLO — a negotiated handshake may
        have consumed the pre-auth push client-side) and surface it."""
        with self._conns_cond:
            self._serve_conns.append(conn)
        conn._last_sent = None
        conn.notify_params()
        if self.on_serve_ready is not None:
            self.on_serve_ready(conn.serve_id)

    def _on_stats(self, conn: _Conn) -> Optional[str]:
        """STATS (read-only telemetry subscribe) hook — only the
        multi-host hub admits stats clients; the plain hub has no live
        run to report on from outside its own process."""
        return ("this hub does not admit stats clients (not a host "
                "transport) — point `repro top` at a training leader")

    @property
    def stats_provider(self) -> Optional[Any]:
        return self._stats_provider

    @stats_provider.setter
    def stats_provider(self, provider: Optional[Any]) -> None:
        """Installing a provider starts the push/history thread at once
        (not lazily with the first subscriber): the history ring must
        already hold cells when a late `repro top` attaches."""
        self._stats_provider = provider
        if provider is not None and not self._closed.is_set():
            self._ensure_stats_thread()

    def stats_history(self) -> List[Dict[str, Any]]:
        """Recent stats cells, oldest first (the backfill payload)."""
        return list(self._stats_history)

    def _on_stats_ready(self, conn: _Conn) -> None:
        """An admitted stats connection just authenticated: send the
        history-ring backfill (so a late-attaching `repro top` can
        compute rates over cells it never saw pushed), then one current
        payload (so it paints before the first cadence tick).  Both go
        out *before* the connection joins the push list — a cadence
        tick must not overtake its own backfill on the wire."""
        history = self.stats_history()
        if history:
            conn.send_frame(
                _stats_frame(json.dumps({"history": history})
                             .encode("utf-8")), lock_timeout=1.0)
        conn.send_frame(self._stats_frame_now(), lock_timeout=1.0)
        with self._conns_cond:
            self._stats_conns.append(conn)
        self._ensure_stats_thread()

    def _stats_frame_now(self, record: bool = False) -> bytes:
        """One STATS push frame from the current provider snapshot.
        A hub whose runtime has not installed a provider yet (or whose
        provider raises mid-teardown) reports a ``waiting`` state
        instead of wedging the push thread.  ``record=True`` (the
        cadence thread) appends real cells to the history ring —
        placeholder ``waiting`` states are never recorded."""
        provider = self._stats_provider
        payload = None
        if provider is not None:
            try:
                payload = provider()
            except Exception:
                payload = None
        if payload is None:
            payload = {"state": "waiting"}
        elif record:
            self._stats_history.append(payload)
        return _stats_frame(json.dumps(payload).encode("utf-8"))

    def _ensure_stats_thread(self) -> None:
        with self._conns_cond:
            if self._stats_thread is not None:
                return
            self._stats_thread = threading.Thread(
                target=self._stats_loop, name="hub-stats", daemon=True)
            self._stats_thread.start()

    def _stats_loop(self) -> None:
        """On every cadence tick: record the current cell in the
        history ring (subscribers or not — that is what a late reader
        backfills from), then push it to every live stats reader.
        Short lock timeout for the same reason as heartbeats: one
        stalled reader must not delay the others' ticks."""
        while not self._closed.wait(self.stats_every_s):
            frame = self._stats_frame_now(record=True)
            with self._conns_cond:
                conns = [c for c in self._stats_conns
                         if not c.closed.is_set()]
            for conn in conns:
                conn.send_frame(frame, lock_timeout=0.2)

    def _heartbeat_loop(self) -> None:
        """PING every authenticated connection on the heartbeat cadence.
        A short lock timeout keeps a writer wedged against one stalled
        peer from delaying liveness for everyone else."""
        frame = _ping_frame()
        while not self._closed.wait(self.heartbeat_s):
            with self._conns_cond:
                conns = [c for c in self._conns
                         if c.authenticated and not c.closed.is_set()]
            for conn in conns:
                conn.send_frame(frame, lock_timeout=0.2)

    def serve_stats(self) -> Dict[str, Any]:
        """Per-serve-client push accounting (the serving-plane half of
        the run report): how many params versions each client was sent,
        the last version it got, and how many pushes the ``serve_every``
        down-sampling skipped."""
        with self._conns_cond:
            conns = list(self._serve_conns)
        with self._conns_cond:
            stats_clients = len(self._stats_conns)
        return {
            "clients": len(conns),
            "rejected_peers": self.rejected_peers,
            "serve_every": self.serve_every,
            "stats_clients": stats_clients,
            "per_client": [
                {"serve_id": c.serve_id,
                 "pushes": c.pushes,
                 "last_version": c.last_pushed_version,
                 "skipped_pushes": c.skipped_pushes,
                 "connected": not c.closed.is_set()}
                for c in conns],
        }

    def _reject(self, conn: _Conn, reason: str) -> None:
        """Turn away a peer with a readable error: logged, counted,
        best-effort REJECT frame (a stray client that can't parse it
        just sees the connection close).  The caller breaks its read
        loop, so the conn closes without ever entering the barrier."""
        try:
            peer = conn.sock.getpeername()
        except OSError:
            peer = "?"
        _log.warning("rejecting peer %s: %s", peer, reason)
        with self._recv_lock:
            self._rejected += 1
        # best-effort only, and never at the cost of the reader: if the
        # write lock is held by a writer wedged against a stalled peer,
        # skip the frame — the close right after this unblocks everyone
        conn.send_frame(_reject_frame(reason), lock_timeout=1.0)

    def _on_hello(self, conn: _Conn) -> None:
        with self._conns_cond:
            self._conns_cond.notify_all()
        # re-arm the params push for this connection: a JOIN handshake
        # may have consumed the pre-HELLO push on the client side (the
        # negotiator reads frames until WELCOME), and a coalesced writer
        # would otherwise never resend the current version
        conn._last_sent = None
        conn.notify_params()
        if self.on_worker_ready is not None:
            self.on_worker_ready(conn.worker_id, conn.generation)

    def _conn_closed(self, conn: _Conn) -> None:
        with self._conns_cond:
            self._conns_cond.notify_all()
        if self.on_worker_gone is not None and conn.worker_id is not None:
            self.on_worker_gone(conn.worker_id, conn.generation)

    def _enqueue(self, msg: GradientMsg) -> bool:
        # bounded put that stays interruptible by close(): the reader
        # blocking here is what turns a full hub queue into socket
        # backpressure all the way to the worker
        while not self._closed.is_set():
            try:
                self._grads.put(msg, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _count_received(self, worker_id: int) -> None:
        with self._recv_lock:
            self._received[worker_id] = \
                self._received.get(worker_id, 0) + 1

    def _note_torn(self) -> None:
        with self._recv_lock:
            self._torn += 1

    # ----------------------------------------------- Transport (server)
    def recv_gradient(self, timeout: Optional[float] = None
                      ) -> Optional[GradientMsg]:
        try:
            if timeout is not None and timeout <= 0:
                return self._grads.get_nowait()
            return self._grads.get(timeout=timeout)
        except queue.Empty:
            return None

    def publish_params(self, msg: ParamsMsg) -> None:
        frame = _params_frame(msg, self.slab_dtype)
        with self._pub_cond:
            # unconditional replace — a restore publishes an OLDER
            # version and workers must resync to it (see Transport)
            self._pub_msg = ParamsMsg(
                msg.version,
                _slab_from_payload(frame, _HDR.size + _PARAMS.size,
                                   self.slab_dtype),
                epoch=msg.epoch)
            self._pub_frames = {self.slab_dtype: frame}
            if self._hold:
                self._held_frame = frame
                self._pub_cond.notify_all()
                return                  # workers see it on release
            self._pub_frame = frame
            self._pub_cond.notify_all()
        self._notify_all_conns()

    def _pub_frame_for(self, dtype_name: str) -> Optional[bytes]:
        """The current publication, encoded for one connection's
        negotiated dtype.  Frames are cached per (publication, dtype):
        the common case — every connection speaks the run's dtype — is
        a dict hit on the frame publish_params already built, and a
        mixed fleet pays one re-encode per foreign dtype per version,
        not per connection.  Returns None while hold_params() is
        withholding the broadcast (the fleet-ready barrier)."""
        with self._pub_cond:
            if self._pub_frame is None:
                return None
            frame = self._pub_frames.get(dtype_name)
            if frame is None and self._pub_msg is not None:
                frame = _params_frame(self._pub_msg, dtype_name)
                self._pub_frames[dtype_name] = frame
            return frame

    def _notify_all_conns(self) -> None:
        with self._conns_cond:
            conns = list(self._conns)
        for conn in conns:
            conn.notify_params()

    def hold_params(self) -> None:
        """Withhold the params broadcast from workers (the hub-local
        cell still updates).  Workers that connect meanwhile block in
        ``fetch_params`` instead of free-running — the fleet-ready
        barrier uses this so no gradient work predates the serving
        clock (which would flatter the multi-process benchmark)."""
        with self._pub_cond:
            self._hold = True
            if self._pub_frame is not None:
                self._held_frame = self._pub_frame
                self._pub_frame = None

    def release_params(self) -> None:
        """Release a :meth:`hold_params` hold: push the latest params
        to every connected worker (the starting gun)."""
        with self._pub_cond:
            self._hold = False
            if self._held_frame is not None:
                self._pub_frame = self._held_frame
                self._held_frame = None
        self._notify_all_conns()

    def pending_gradients(self) -> int:
        return self._grads.qsize()

    # --------------------------------------------- Transport (loopback)
    def send_gradient(self, msg: GradientMsg,
                      timeout: Optional[float] = None) -> bool:
        try:
            if timeout is not None and timeout <= 0:
                self._grads.put_nowait(msg)
            else:
                self._grads.put(msg, timeout=timeout)
        except queue.Full:
            return False
        self._count_received(msg.worker_id)
        return True

    def fetch_params(self, min_version: int = 0,
                     timeout: Optional[float] = None
                     ) -> Optional[ParamsMsg]:
        with self._pub_cond:
            ok = self._pub_cond.wait_for(
                lambda: self._pub_msg is not None
                and self._pub_msg.version >= min_version,
                0 if (timeout is not None and timeout <= 0) else timeout)
            return self._pub_msg if ok else None

    # ------------------------------------------------------- lifecycle
    def connect(self, worker_id: int, generation: int = 0,
                send_capacity: int = 2) -> "SocketWorkerClient":
        """A worker-side endpoint in this process (thread workers) —
        speaking the run's slab dtype."""
        return SocketWorkerClient(self.address, worker_id,
                                  generation=generation,
                                  family=self.family,
                                  send_capacity=send_capacity,
                                  slab_dtype=self.slab_dtype)

    def wait_for_workers(self, n: int,
                         timeout: Optional[float] = None) -> bool:
        """Block until ``n`` distinct workers have said HELLO and are
        still connected (process workers connect only after their JAX
        runtime is warm, so this is the fleet-ready barrier)."""
        def ready() -> bool:
            live = {c.worker_id for c in self._conns
                    if c.worker_id is not None and not c.closed.is_set()}
            return len(live) >= n
        with self._conns_cond:
            return self._conns_cond.wait_for(ready, timeout)

    def live_workers(self) -> Set[int]:
        with self._conns_cond:
            return {c.worker_id for c in self._conns
                    if c.worker_id is not None and not c.closed.is_set()}

    def connected_workers(self) -> Dict[int, int]:
        """{worker_id: generation} of every live, HELLO'd connection —
        the runtime sweeps this after installing its membership hooks,
        catching externally-joined workers whose HELLO landed first."""
        with self._conns_cond:
            return {c.worker_id: c.generation for c in self._conns
                    if c.worker_id is not None
                    and not c.closed.is_set()}

    def received_counts(self) -> Dict[int, int]:
        """Complete gradient frames received, per worker id — the exact
        "computed" ledger column for process workers.  Read only after
        :meth:`quiesce` returned ``True``."""
        with self._recv_lock:
            return dict(self._received)

    @property
    def torn_frames(self) -> int:
        """Frames discarded because the sender died mid-write."""
        with self._recv_lock:
            return self._torn

    @property
    def rejected_peers(self) -> int:
        """Connections turned away for violating the wire protocol
        (bad magic, version mismatch, malformed first frame)."""
        with self._recv_lock:
            return self._rejected

    def half_close_workers(self) -> None:
        """Send EOF to every worker (params direction) while still
        draining their in-flight gradient frames — the clean-shutdown
        signal for process workers.  Workers that connect *after* this
        call are half-closed on arrival (see the accept loop), so a
        late-starting respawn can never outlive the run."""
        self._draining = True
        with self._conns_cond:
            conns = list(self._conns)
        for conn in conns:
            conn.half_close()

    def quiesce(self, timeout: Optional[float] = None) -> bool:
        """True once every connection reader has drained to EOF (all
        producers must already be stopped/closed).  Interleave with
        ``recv_gradient(timeout=0)`` drains: a reader blocked on the
        bounded queue needs the caller to make room.  Serve and stats
        connections are skipped: they produce no gradients, so the
        conservation ledger owes them nothing — and a lingering
        read-only subscriber must never hold up training shutdown."""
        deadline = None if timeout is None else \
            time.monotonic() + max(0.0, timeout)
        with self._conns_cond:
            conns = [c for c in self._conns
                     if not c.is_serve and not c.is_stats]
        for conn in conns:
            remain = None if deadline is None else \
                max(0.0, deadline - time.monotonic())
            conn.reader.join(timeout=remain)
            if conn.reader.is_alive():
                return False
        return True

    def close(self) -> None:
        self._closed.set()
        try:
            self._lsock.close()
        except OSError:
            pass
        with self._conns_cond:
            conns = list(self._conns)
        for conn in conns:
            conn.close()
        self._accept_thread.join(timeout=2.0)
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=2.0)
        if self._stats_thread is not None:
            self._stats_thread.join(timeout=2.0)
        if self.family == "unix":
            for path in (self.address,):
                try:
                    os.unlink(path)
                except OSError:
                    pass
            if self._sockdir:
                try:
                    os.rmdir(self._sockdir)
                except OSError:
                    pass


# ======================================================== worker side


class SocketWorkerClient:
    """The worker half of the protocol over one socket connection.

    ``send_gradient`` enqueues into a small bounded outbound queue
    drained by a sender thread (so a timed-out send never leaves a torn
    frame on the wire — the frame is sent whole or not at all), and
    ``fetch_params`` waits on a local versioned cell kept current by a
    reader thread — the same broadcast-cell semantics as
    :class:`InProcTransport`.

    :attr:`closed` is set when the connection dies (server shutdown,
    kill, network error); runtimes wire it up as the worker's stop
    event so a dead server can never leave a live worker spinning.

    ``heartbeat_timeout_s > 0`` arms a liveness watchdog: if *no* frame
    (params, PING, anything) arrives for that long, the leader is
    declared hung — a state EOF detection can never see, because a
    wedged process holds its sockets open — :attr:`stall_reason` is set
    with a readable error and the connection closes, which stops the
    worker through the usual dead-server path.
    """

    def __init__(self, address: Any, worker_id: int, *,
                 generation: int = 0, family: str = "unix",
                 send_capacity: int = 2, connect_timeout: float = 10.0,
                 heartbeat_timeout_s: float = 0.0,
                 sock: Optional[socket.socket] = None,
                 slab_dtype: str = "f32"):
        if slab_dtype not in _DT_CODES:
            raise ValueError(f"slab_dtype must be one of "
                             f"{sorted(_DT_CODES)}, got {slab_dtype!r}")
        self.worker_id = worker_id
        self.generation = generation
        self.slab_dtype = slab_dtype
        self.reject_reason: Optional[str] = None
        self.stall_reason: Optional[str] = None
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self._last_rx = time.monotonic()
        if sock is None:
            if family == "unix":
                sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                sock.settimeout(connect_timeout)
                sock.connect(address)
            else:
                sock = socket.create_connection(tuple(address),
                                                timeout=connect_timeout)
        # else: adopt an already-connected socket (e.g. the one a JOIN
        # handshake negotiated the worker-id lease on — see hostlink)
        sock.settimeout(None)
        _configure(sock)
        self.sock = sock
        self.closed = threading.Event()
        self._cell: Optional[ParamsMsg] = None
        self._cond = threading.Condition()
        self._sendq: "queue.Queue[GradientMsg]" = \
            queue.Queue(maxsize=max(1, send_capacity))
        self._close_lock = threading.Lock()
        self._closed_once = False
        self._wlock = threading.Lock()      # whole frames only: the
        #                                     sender thread and PONG
        #                                     replies share one socket
        self.sock.sendall(_hello_frame(worker_id, generation,
                                       slab_dtype))
        self._reader = threading.Thread(
            target=self._read_loop, name=f"client-reader-{worker_id}",
            daemon=True)
        self._sender = threading.Thread(
            target=self._send_loop, name=f"client-sender-{worker_id}",
            daemon=True)
        self._reader.start()
        self._sender.start()
        if self.heartbeat_timeout_s > 0:
            threading.Thread(target=self._watchdog_loop,
                             name=f"client-watchdog-{worker_id}",
                             daemon=True).start()

    # ------------------------------------------------------ wire threads
    def _read_loop(self) -> None:
        try:
            while not self.closed.is_set():
                hdr, _ = _recv_exact(self.sock, _HDR.size)
                if hdr is None:
                    break
                ftype, n = _HDR.unpack(hdr)
                if n > _MAX_FRAME:
                    break
                payload, _ = _recv_exact(self.sock, n)
                if payload is None:
                    break
                self._last_rx = time.monotonic()
                if ftype == _F_PING:
                    # reply best-effort; the hub only cares that bytes
                    # flow back, and a send error surfaces on the next
                    # gradient anyway
                    with self._wlock:
                        try:
                            self.sock.sendall(_pong_frame())
                        except OSError:
                            break
                elif ftype == _F_PARAMS and n >= _PARAMS.size \
                        and (n - _PARAMS.size) \
                        % _SLAB_ITEMSIZE[self.slab_dtype] == 0:
                    version, epoch = _PARAMS.unpack(
                        payload[:_PARAMS.size])
                    slab = _slab_from_payload(payload, _PARAMS.size,
                                              self.slab_dtype)
                    with self._cond:
                        self._cell = ParamsMsg(version, slab,
                                               epoch=epoch)
                        self._cond.notify_all()
                elif ftype == _F_REJECT:
                    reason = payload[_CTRL.size:].decode(
                        "utf-8", "replace") if n >= _CTRL.size else ""
                    self.reject_reason = reason or "rejected by hub"
                    _log.warning("hub rejected worker %d.%d: %s",
                                 self.worker_id, self.generation,
                                 self.reject_reason)
                    break
        finally:
            self._mark_closed()

    def _send_loop(self) -> None:
        while True:
            try:
                msg = self._sendq.get(timeout=0.1)
            except queue.Empty:
                if self.closed.is_set():
                    return
                continue
            try:
                with self._wlock:
                    self.sock.sendall(_grad_frame(msg,
                                                  self.slab_dtype))
            except OSError:
                # the frame was accepted but never shipped: do NOT
                # task_done() it — flush() must not claim it landed
                self._mark_closed()
                return
            self._sendq.task_done()

    def _watchdog_loop(self) -> None:
        """Declare the leader hung when no frame of any kind arrives
        within ``heartbeat_timeout_s`` — then close, so every blocked
        path (fetch_params, the worker loop) unwinds promptly."""
        timeout = self.heartbeat_timeout_s
        while not self.closed.wait(min(timeout / 4.0, 1.0)):
            idle = time.monotonic() - self._last_rx
            if idle > timeout:
                self.stall_reason = (
                    f"no frames from the hub for {idle:.1f}s (liveness "
                    f"timeout {timeout:.1f}s) — the leader looks hung; "
                    "giving up on this connection")
                _log.warning("worker %d.%d: %s", self.worker_id,
                             self.generation, self.stall_reason)
                self.close()
                return

    def _mark_closed(self) -> None:
        self.closed.set()
        with self._cond:
            self._cond.notify_all()         # wake blocked fetch_params

    # ------------------------------------------- Transport (worker half)
    def send_gradient(self, msg: GradientMsg,
                      timeout: Optional[float] = None) -> bool:
        if timeout is not None and timeout <= 0:
            if self.closed.is_set():
                return False
            try:
                self._sendq.put_nowait(msg)
                return True
            except queue.Full:
                return False
        deadline = None if timeout is None else \
            time.monotonic() + timeout
        while not self.closed.is_set():
            remain = None if deadline is None else \
                deadline - time.monotonic()
            if remain is not None and remain <= 0:
                return False
            try:
                self._sendq.put(msg, timeout=0.05 if remain is None
                                else min(0.05, remain))
                return True
            except queue.Full:
                continue
        return False

    def fetch_params(self, min_version: int = 0,
                     timeout: Optional[float] = None
                     ) -> Optional[ParamsMsg]:
        def ok() -> bool:
            return (self._cell is not None
                    and self._cell.version >= min_version)
        with self._cond:
            if timeout is not None and timeout <= 0:
                return self._cell if ok() else None
            deadline = None if timeout is None else \
                time.monotonic() + timeout
            while not ok():
                if self.closed.is_set():
                    return None
                remain = None if deadline is None else \
                    deadline - time.monotonic()
                if remain is not None and remain <= 0:
                    return None
                self._cond.wait(0.1 if remain is None
                                else min(0.1, remain))
            return self._cell

    def pending_gradients(self) -> int:
        return self._sendq.qsize()

    # the worker half never receives gradients or publishes params
    def recv_gradient(self, timeout: Optional[float] = None):
        raise NotImplementedError("worker-side endpoint")

    def publish_params(self, msg: ParamsMsg) -> None:
        raise NotImplementedError("worker-side endpoint")

    # ------------------------------------------------------- lifecycle
    def flush(self, timeout: float = 5.0) -> bool:
        """Block until every accepted gradient is on the wire — clean
        shutdown must not strand sent-but-unshipped gradients (the
        ledger counts them as computed).  Note this waits on the sender
        *thread*, not on :attr:`closed`: a hub half-close (EOF on the
        params direction) sets ``closed`` while the gradient direction
        is still perfectly writable, and bailing there would tear the
        final frames."""
        deadline = time.monotonic() + timeout
        while self._sendq.unfinished_tasks:
            if not self._sender.is_alive() \
                    or time.monotonic() > deadline:
                return self._sendq.unfinished_tasks == 0
            time.sleep(0.01)
        return True

    def can_flush(self) -> bool:
        """Whether unshipped frames can still make progress — the
        sender thread is alive.  A dead sender means the connection is
        gone and the remaining frames are lost; waiting on them is
        pointless."""
        return self._sender.is_alive()

    def quiesce(self, timeout: Optional[float] = None) -> bool:
        return self.flush(timeout if timeout is not None else 5.0)

    def close(self) -> None:
        with self._close_lock:
            if self._closed_once:
                return
            self._closed_once = True
        self._mark_closed()
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


# ================================================== process launcher


def worker_process_platform() -> str:
    """``JAX_PLATFORMS`` for a worker process spawned on this host:
    always ``"cpu"``.  One accelerator serves one process, and this one
    already holds it (or there is none), so a same-host child computes
    on the host CPU.  Under an accelerator parent that is the fallback
    that would pass for a chip run, so it warns, and the runtime
    records the platform per worker (``RunResult.extra["placement"]``).
    Pinning one worker process to each chip is still to be built."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        warnings.warn(
            f"worker processes compute on the CPU: this process holds "
            f"the {backend} device and one accelerator serves one "
            "process", RuntimeWarning, stacklevel=2)
    return "cpu"


@dataclasses.dataclass
class ProcWorkerConfig:
    """Everything a worker process needs to rebuild its world: the
    experiment spec (to rebuild the workload via the ``SIM_WORKLOADS``
    registry — code does not cross the process boundary, only this
    picklable description does), its identity/shard, and the hub
    address.  ``platform`` forces ``JAX_PLATFORMS`` in the child (see
    :func:`worker_process_platform`)."""
    spec: Dict[str, Any]
    worker_id: int
    generation: int
    num_workers: int
    mode: str
    straggle_s: float
    seed: int
    batch: int
    address: Any = None
    family: str = "unix"
    platform: Optional[str] = None


def _proc_worker_main(cfg: ProcWorkerConfig) -> None:
    """Child entry point: rebuild the workload, compile the slab
    gradient executable, and only then connect (HELLO == ready), so the
    parent's wall-clock budget measures contention — not XLA."""
    if cfg.platform:
        os.environ["JAX_PLATFORMS"] = cfg.platform
    try:
        from repro.api.spec import ExperimentSpec
        from repro.cluster.hostlink import build_slab_worker_fn
        from repro.cluster.worker import Worker

        spec = ExperimentSpec.from_dict(cfg.spec)
        grad, fresh_batches = build_slab_worker_fn(
            spec, cfg.worker_id, cfg.num_workers, cfg.generation,
            batch=cfg.batch, seed=cfg.seed)
        client = SocketWorkerClient(cfg.address, cfg.worker_id,
                                    generation=cfg.generation,
                                    family=cfg.family,
                                    slab_dtype=spec.slab_dtype)
    except Exception:
        import traceback
        traceback.print_exc()
        sys.stderr.flush()
        os._exit(2)

    worker = Worker(cfg.worker_id, grad_fn=grad,
                    batches=fresh_batches(), transport=client,
                    mode=cfg.mode, straggle_s=cfg.straggle_s,
                    generation=cfg.generation)
    # server shutdown/death closes the connection -> closed is set ->
    # the loop exits: a dead server can never leave this process alive
    worker.stop_event = client.closed
    worker.run()                            # inline, not as a thread
    client.flush(5.0)
    client.close()
    code = 0
    if worker.error:
        print(worker.error, file=sys.stderr, flush=True)
        code = 3
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter finalization: tearing down a JAX runtime's C++
    # thread pools from a fast-exiting spawned child intermittently
    # aborts (std::terminate) after all real work is already flushed
    os._exit(code)


class ProcTransport(SocketTransport):
    """The multi-process transport: a Unix-domain (or TCP) socket hub
    plus a ``multiprocessing`` *spawn* launcher — each worker is a
    fresh OS process with its own JAX runtime that connects back to the
    hub once compiled.  ``FaultPlan`` kills are **SIGKILL**: worker
    death is an OS fact, and the hub's torn-frame handling plus
    received-side accounting keep the conservation ledger exact through
    it.  Spawn (not fork) because forking a process with a live JAX
    runtime is undefined behaviour."""

    def __init__(self, grad_capacity: int = 0, *, family: str = "unix",
                 host: str = "127.0.0.1", slab_dtype: str = "f32"):
        super().__init__(grad_capacity, family=family, host=host,
                         slab_dtype=slab_dtype)
        import multiprocessing
        self._ctx = multiprocessing.get_context("spawn")
        self._procs: Dict[int, Any] = {}            # live, by worker id
        self._all_procs: List[Tuple[int, int, Any]] = []
        self._killed: Set[int] = set()              # pids we SIGKILLed

    # -------------------------------------------------------- processes
    def spawn_worker(self, cfg: ProcWorkerConfig):
        cfg = dataclasses.replace(cfg, address=self.address,
                                  family=self.family)
        p = self._ctx.Process(
            target=_proc_worker_main, args=(cfg,),
            name=f"worker-{cfg.worker_id}.{cfg.generation}", daemon=True)
        p.start()
        self._procs[cfg.worker_id] = p
        self._all_procs.append((cfg.worker_id, cfg.generation, p))
        return p

    def kill_worker(self, worker_id: int) -> bool:
        """SIGKILL the worker's current process (no cooperation, no
        cleanup — the fault the paper's cluster baseline worries
        about).  Returns True if a live process was signalled."""
        p = self._procs.get(worker_id)
        if p is None or not p.is_alive():
            return False
        self._killed.add(p.pid)
        p.kill()
        return True

    def procs_alive(self) -> bool:
        """Any spawned worker process still running?"""
        return any(p.is_alive() for _, _, p in self._all_procs)

    def kill_unconnected(self) -> None:
        """SIGKILL worker processes that never finished connecting —
        e.g. a respawned worker still importing JAX / compiling when
        the run ends.  They have sent nothing, so there is nothing to
        flush or account; the EOF-based shutdown can't reach them (no
        connection), and waiting out their startup would stall
        teardown.  Planned kills, not errors."""
        with self._conns_cond:
            connected = {(c.worker_id, c.generation)
                         for c in self._conns
                         if c.worker_id is not None}
        for wid, gen, p in self._all_procs:
            if p.is_alive() and (wid, gen) not in connected:
                self._killed.add(p.pid)
                p.kill()

    def dead_workers(self) -> List[str]:
        """Processes that already exited abnormally (no planned SIGKILL)
        — lets the fleet-ready barrier fail fast instead of waiting out
        its timeout on a child that crashed during startup."""
        out = []
        for wid, gen, p in self._all_procs:
            code = p.exitcode
            if code is None or code == 0:
                continue
            if code < 0 and p.pid in self._killed:
                continue
            out.append(f"worker process {wid}.{gen} exited with code "
                       f"{code} (see its stderr above)")
        return out

    def join_workers(self, timeout: float = 10.0) -> List[str]:
        """Join every spawned process, escalating to SIGKILL past the
        deadline.  Returns human-readable errors for processes that
        failed (crashed with a traceback) rather than exited cleanly or
        by a planned SIGKILL."""
        errors: List[str] = []
        deadline = time.monotonic() + timeout
        for wid, gen, p in self._all_procs:
            p.join(timeout=max(0.0, deadline - time.monotonic()))
            if p.is_alive():
                self._killed.add(p.pid)
                p.kill()
                p.join(timeout=2.0)
                errors.append(f"worker process {wid}.{gen} did not stop "
                              "within the join timeout (SIGKILLed)")
                continue
            code = p.exitcode
            planned_kill = (code is not None and code < 0
                            and p.pid in self._killed)
            if code not in (0, None) and not planned_kill:
                errors.append(f"worker process {wid}.{gen} exited with "
                              f"code {code} (see its stderr above)")
        return errors

    def close(self) -> None:
        for _, _, p in self._all_procs:
            if p.is_alive():
                self._killed.add(p.pid)
                p.kill()
        for _, _, p in self._all_procs:
            if p.is_alive():
                p.join(timeout=2.0)
        super().close()
