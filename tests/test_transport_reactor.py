"""Reactor transport: incremental frame reassembly under arbitrary
byte splits, torn-frame disconnects, header-time admission shedding,
and reactor/threads fixed-seed parity.

The reactor's hardening guarantee is structural — ``_frame_parser``
is the SAME generator ``recv_msg`` drives — but these tests pin the
part that is new: the reassembly state machine must produce identical
frames (and identical failures) no matter where epoll happens to cut
the byte stream.
"""

import queue as queue_lib
import socket
import struct
import threading
import time

import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.distributed import (
    transport as transport_mod,
)
from actor_critic_algs_on_tensorflow_tpu.distributed.tenancy import (
    TenantAdmission,
)
from actor_critic_algs_on_tensorflow_tpu.distributed.transport import (
    KIND_ACK,
    KIND_GET_PARAMS,
    KIND_TRAJ,
    MAGIC,
    MAX_NDIM,
    ActorClient,
    ChecksumError,
    LearnerServer,
    _frame_parser,
    _RxState,
    pack_arrays,
)
from tests.helpers import time_limit


class _ScriptedSock:
    """Fake non-blocking socket: serves a byte stream in scripted
    chunk sizes, then raises BlockingIOError (or returns EOF)."""

    def __init__(self, data: bytes, splits, eof: bool = False):
        self._chunks = []
        at = 0
        for n in splits:
            self._chunks.append(data[at : at + n])
            at += n
        if at < len(data):
            self._chunks.append(data[at:])
        self._eof = eof

    def recv(self, n: int) -> bytes:
        if not self._chunks:
            if self._eof:
                return b""
            raise BlockingIOError
        chunk = self._chunks[0]
        take, keep = chunk[:n], chunk[n:]
        if keep:
            self._chunks[0] = keep
        else:
            self._chunks.pop(0)
        return take

    def recv_into(self, view, n: int) -> int:
        got = self.recv(n)
        view[: len(got)] = got
        return len(got)


def _pump_all(data: bytes, splits, eof: bool = False):
    """Drive _RxState over ``data`` cut at ``splits``; return the
    completed frames."""
    frames = []
    rx = _RxState(lambda: _frame_parser())
    sock = _ScriptedSock(data, splits, eof=eof)
    while True:
        try:
            rx.pump(sock, lambda *f: frames.append(f))
        except BlockingIOError:
            pass
        if not sock._chunks:
            if eof:
                # One more pass to observe the EOF.
                rx.pump(sock, lambda *f: frames.append(f))
            break
    return frames


def _example_frame() -> tuple:
    arrays = [
        np.arange(12, dtype=np.float32).reshape(3, 4),
        np.array(7, dtype=np.int64),                # 0-d: zero-need reqs
        np.zeros((2, 0, 5), dtype=np.uint8),        # empty payload
        np.array([True, False, True]),
    ]
    return arrays, bytes(pack_arrays(KIND_TRAJ, 42, arrays))


def _assert_frame(frame, arrays, tag=42):
    kind, got_tag, got, nbytes = frame
    assert kind == KIND_TRAJ and got_tag == tag
    assert nbytes == sum(int(a.nbytes) for a in arrays)
    assert len(got) == len(arrays)
    for x, y in zip(arrays, got):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


def test_reassembly_every_single_split():
    """The frame parses identically for EVERY possible split point —
    including cuts inside the magic, the frame header, each array
    header field, and each CRC."""
    arrays, data = _example_frame()
    for at in range(1, len(data)):
        frames = _pump_all(data, [at])
        assert len(frames) == 1, f"split at {at}"
        _assert_frame(frames[0], arrays)


def test_reassembly_random_multisplits_and_coalesced_frames():
    """Seeded random chunkings — including byte-at-a-time and several
    frames coalesced into one stream — reassemble exactly."""
    arrays, data = _example_frame()
    stream = data * 3
    rng = np.random.default_rng(20)
    plans = [[1] * len(stream)]  # fully torn: one byte per readiness
    for _ in range(25):
        n_cuts = int(rng.integers(1, 12))
        cuts = sorted(
            int(x) for x in rng.integers(1, len(stream), size=n_cuts)
        )
        splits, prev = [], 0
        for c in cuts:
            if c > prev:
                splits.append(c - prev)
                prev = c
        plans.append(splits)
    for splits in plans:
        frames = _pump_all(stream, splits)
        assert len(frames) == 3
        for frame in frames:
            _assert_frame(frame, arrays)


def test_hostile_headers_fail_identically_under_splits():
    """Garbage that the blocking path rejects is rejected by the
    incremental parser at every chunking — the hardening is shared,
    not re-implemented."""
    def header(kind, tag, n):
        return struct.pack(">4sBQI", MAGIC, kind, tag, n)

    cases = [
        b"XXXX" + b"\x00" * 13,                       # bad magic
        header(KIND_TRAJ, 0, 2**31),                  # absurd n_arrays
        header(KIND_TRAJ, 0, 1)                       # over budget
        + struct.pack(">B", 3) + b"<f4"
        + struct.pack(">B", 1) + struct.pack(">Q", 2**40)
        + struct.pack(">Q", 2**42),
        header(KIND_TRAJ, 0, 1)                       # rank overflow
        + struct.pack(">B", 3) + b"<f4"
        + struct.pack(">B", MAX_NDIM + 1),
        header(KIND_TRAJ, 0, 1)                       # shape/nbytes lie
        + struct.pack(">B", 3) + b"<f4"
        + struct.pack(">B", 1) + struct.pack(">Q", 3)
        + struct.pack(">Q", 16) + b"\x00" * 16,
        header(KIND_TRAJ, 0, 1)                       # garbage dtype
        + struct.pack(">B", 4) + b"\xff\xfe\x00\x01",
    ]
    for data in cases:
        for splits in ([len(data)], [1] * len(data), [5]):
            with pytest.raises(ConnectionError):
                _pump_all(data, splits)


def test_crc_mismatch_across_split():
    """A payload corrupted in flight raises ChecksumError even when
    the stream is cut right at (and inside) the CRC trailer."""
    arrays, data = _example_frame()
    # Flip a byte inside the first payload (after the 17B frame header
    # and the first 15B array header: 1+3+1+8+8 then 4B CRC... corrupt
    # a byte well inside the 48-byte f32 payload instead of computing
    # offsets: the first payload is the first 48-byte run after the
    # CRC; locate it by searching for the encoded arange bytes.
    payload = arrays[0].tobytes()
    at = data.index(payload)
    bad = bytearray(data)
    bad[at + 5] ^= 0xFF
    bad = bytes(bad)
    for splits in ([len(bad)], [1] * len(bad), [at + 20]):
        with pytest.raises(ChecksumError):
            _pump_all(bad, splits)


def test_torn_frame_disconnect_mid_reassembly():
    """EOF with a frame partially reassembled is the same
    'peer closed mid-frame' ConnectionError the blocking path raises
    — at a header boundary, mid-array-header, and mid-payload."""
    _, data = _example_frame()
    for cut in (3, 17, 25, len(data) - 7):
        with pytest.raises(ConnectionError, match="peer closed"):
            _pump_all(data[:cut], [cut], eof=True)


def test_header_time_shed_skips_buffering_and_crc():
    """With the probe over budget the parser validates array headers
    but never buffers payloads: arrays comes back None, a corrupt CRC
    goes unnoticed (the bytes are going nowhere), and the byte count
    still meters the full payload."""
    arrays, data = _example_frame()
    bad = bytearray(data)
    payload = arrays[0].tobytes()
    bad[bad.index(payload) + 1] ^= 0xFF  # would fail CRC if checked
    probed = []

    def drive(data, shed):
        rx = _RxState(lambda: _frame_parser(
            shed_probe=lambda k, t, n: (probed.append((k, t, n)), shed)[1]
        ))
        frames = []
        rx.pump(
            _ScriptedSock(bytes(data), [1] * len(data)),
            lambda *f: frames.append(f),
        )
        return frames

    frames = drive(bad, True)
    assert len(frames) == 1
    kind, tag, got, nbytes = frames[0]
    assert kind == KIND_TRAJ and tag == 42
    assert got is None
    assert nbytes == sum(int(a.nbytes) for a in arrays)
    assert probed[-1] == (KIND_TRAJ, 42, len(arrays))
    # Same bytes with the probe under budget: the CRC fires.
    with pytest.raises(ChecksumError):
        drive(bad, False)


def _collect_server(mode, sunk):
    server = LearnerServer(
        lambda traj, ep: (sunk.append([np.asarray(x) for x in traj]),
                          True)[1],
        server_io_mode=mode,
        log=lambda m: None,
    )
    return server


@pytest.mark.parametrize("mode", ["reactor", "threads"])
def test_push_roundtrip_both_modes(mode):
    """The same pushes land identically through either receive driver
    (the fallback stays live, the default stays correct)."""
    sunk = []
    server = _collect_server(mode, sunk)
    rng = np.random.default_rng(11)
    sent = []
    client = ActorClient("127.0.0.1", server.port)
    for i in range(4):
        traj = [rng.random((5, 3)).astype(np.float32),
                np.full((2,), i, np.int64)]
        sent.append(traj)
        client.push_trajectory(traj, [np.zeros(1, np.float32)])
    client.close()
    deadline = time.monotonic() + 5.0
    while len(sunk) < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    m = server.metrics()
    server.close()
    assert len(sunk) == 4
    for got, want in zip(sunk, sent):
        for x, y in zip(got, want):
            np.testing.assert_array_equal(x, y)
    assert m["transport_trajectories"] == 4
    if mode == "reactor":
        assert m["transport_io_threads"] == 1
        assert m["transport_reactor_wakeups"] > 0
    else:
        assert m["transport_io_threads"] >= 1


def test_mixed_fleet_fixed_seed_parity():
    """Parity pin: one reactor server and one threads server fed the
    SAME seeded frame sequence produce byte-identical sink contents
    and identical ingest counters — the wire behavior of the two
    drivers is indistinguishable."""
    def run(mode):
        sunk = []
        server = _collect_server(mode, sunk)
        rng = np.random.default_rng(2026)
        client = ActorClient("127.0.0.1", server.port)
        for i in range(6):
            traj = [
                rng.random((4, 2)).astype(np.float32),
                (rng.integers(0, 99, size=(3,))).astype(np.int64),
            ]
            client.push_trajectory(traj, [np.zeros(1, np.float32)])
        client.close()
        # Wait for the goodbye frame too: the counters are compared
        # exactly, so both drivers must have seen the whole sequence.
        deadline = time.monotonic() + 5.0
        while (
            len(sunk) < 6
            or server.metrics()["transport_graceful_closes"] < 1
        ) and time.monotonic() < deadline:
            time.sleep(0.01)
        m = server.metrics()
        server.close()
        return sunk, m

    r_sunk, r_m = run("reactor")
    t_sunk, t_m = run("threads")
    assert len(r_sunk) == len(t_sunk) == 6
    for a, b in zip(r_sunk, t_sunk):
        for x, y in zip(a, b):
            assert x.tobytes() == y.tobytes()
    for key in ("transport_trajectories", "transport_frames_in",
                "transport_graceful_closes"):
        assert r_m[key] == t_m[key], key


def test_reactor_sheds_over_budget_at_header():
    """Server-level header shed: the probe marks the peer over budget,
    the sink never runs, the shed counter advances, and the push is
    still ACKed (the client is throttled, not broken)."""
    sunk = []
    server = LearnerServer(
        lambda traj, ep: (sunk.append(1), True)[1],
        server_io_mode="reactor",
        log=lambda m: None,
    )
    metered = []

    def admit(peer, nbytes):
        metered.append(nbytes)
        return False  # frame-end metering agrees: shed

    server.set_admission_handler(admit, probe=lambda peer: True)
    client = ActorClient("127.0.0.1", server.port)
    traj = [np.ones((8, 4), np.float32)]
    client.push_trajectory(traj, [np.zeros(1, np.float32)])
    client.push_trajectory(traj, [np.zeros(1, np.float32)])
    client.close()
    deadline = time.monotonic() + 5.0
    while server.metrics()["transport_shed_frames"] < 2 and (
        time.monotonic() < deadline
    ):
        time.sleep(0.01)
    m = server.metrics()
    server.close()
    assert m["transport_shed_frames"] == 2
    assert not sunk
    assert len(metered) == 2  # frame-end metering still ran


def test_reactor_survives_hostile_peer_and_keeps_serving():
    """A raw socket spraying garbage magic is dropped by the reactor
    without taking the loop (or any other connection) down."""
    sunk = []
    server = _collect_server("reactor", sunk)
    hostile = socket.create_connection(("127.0.0.1", server.port))
    hostile.sendall(b"XXXX" + b"\x00" * 13)
    client = ActorClient("127.0.0.1", server.port)
    client.push_trajectory(
        [np.ones((3,), np.float32)], [np.zeros(1, np.float32)]
    )
    client.close()
    hostile.close()
    deadline = time.monotonic() + 5.0
    while len(sunk) < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    alive = server.alive
    server.close()
    assert sunk and alive


def test_pump_budget_yields_and_resumes(monkeypatch):
    """Fairness: with a tiny budget, one pump pass returns to the
    selector with socket bytes still unread (a firehose peer can't
    monopolize the readiness pass), and the next pass resumes exactly
    where it left off — all frames still land intact."""
    monkeypatch.setattr(transport_mod, "_PUMP_BUDGET_BYTES", 64)
    arrays, payload = _example_frame()
    data = payload * 3
    frames = []
    rx = _RxState(lambda: _frame_parser())
    # Many small chunks, so the budget can bite between recvs.
    sock = _ScriptedSock(data, [50] * (len(data) // 50))
    rx.pump(sock, lambda *f: frames.append(f))
    assert sock._chunks, "budget did not bound the pass"
    passes = 1
    while sock._chunks:
        rx.pump(sock, lambda *f: frames.append(f))
        passes += 1
        assert passes < 1000
    # Nothing buffered unread inside rx between passes would show up
    # here as a missing/short frame.
    rx.pump(sock, lambda *f: frames.append(f))
    assert passes > 1
    assert len(frames) == 3
    for frame in frames:
        _assert_frame(frame, arrays)


def test_reactor_handler_fault_costs_one_connection():
    """A sink bug (ValueError on a malformed trajectory) retires the
    offending connection only — threads-mode blast radius — and the
    loop keeps serving everyone else."""
    calls = []

    def sink(traj, ep):
        calls.append(1)
        if len(calls) == 1:
            raise ValueError("malformed trajectory")
        return True

    server = LearnerServer(
        sink, server_io_mode="reactor", log=lambda m: None
    )
    with time_limit(20.0, "handler-fault isolation"):
        bad = ActorClient("127.0.0.1", server.port)
        with pytest.raises((ConnectionError, OSError)):
            bad.push_trajectory(
                [np.ones((3,), np.float32)], [np.zeros(1, np.float32)]
            )
        bad.close()
        good = ActorClient("127.0.0.1", server.port)
        good.push_trajectory(
            [np.ones((3,), np.float32)], [np.zeros(1, np.float32)]
        )
        good.close()
        alive = server.alive
        server.close()
    assert alive
    assert len(calls) == 2


def test_reactor_slow_param_fetcher_does_not_block_loop(monkeypatch):
    """HOL-blocking pin: a peer that requests full params and never
    reads them must not stall the loop — another client's pushes keep
    ACKing while the send sits buffered, and the stall sweep recycles
    the wedged connection (transport_send_stalls)."""
    monkeypatch.setattr(transport_mod, "_SEND_STALL_S", 2.0)
    server = LearnerServer(
        lambda traj, ep: True,
        server_io_mode="reactor",
        log=lambda m: None,
    )
    with time_limit(30.0, "slow param fetcher"):
        # Params far larger than the peer's socket buffers, so the
        # send MUST tail-buffer on the server.
        server.publish(
            [np.zeros(4_000_000, np.float32)], notify=False
        )
        wedged = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        wedged.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        wedged.connect(("127.0.0.1", server.port))
        wedged.sendall(bytes(pack_arrays(KIND_GET_PARAMS, 0, [])))
        # Let the reactor dispatch the fetch and wedge the reply.
        time.sleep(0.2)
        client = ActorClient("127.0.0.1", server.port)
        t0 = time.monotonic()
        for _ in range(3):
            client.push_trajectory(
                [np.ones((4,), np.float32)], [np.zeros(1, np.float32)]
            )
        elapsed = time.monotonic() - t0
        client.close()
        # Head-of-line blocked sends would serialize these behind the
        # wedged 8 MB param frame (>= the 2 s stall deadline).
        assert elapsed < 2.0, f"pushes took {elapsed:.2f}s"
        deadline = time.monotonic() + 10.0
        while (
            server.metrics()["transport_send_stalls"] < 1
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        m = server.metrics()
        wedged.close()
        server.close()
    assert m["transport_send_stalls"] >= 1


def test_header_shed_attribution_survives_bucket_refill():
    """Finding-5 pin: when the transport sheds at header time but the
    tenant's bucket has tokens by frame end, the shed hook still
    records the drop as SHED — per-tenant meters agree with
    transport_shed_frames instead of claiming admission for a payload
    that was drained to scratch."""
    # Generous budget: admit_frame WOULD say "admitted" — the old
    # disagreement path — so only record_shed keeps the books honest.
    adm = TenantAdmission(default_mb_s=1000.0, log=lambda m: None)
    server = LearnerServer(
        lambda traj, ep: True,
        server_io_mode="reactor",
        log=lambda m: None,
    )
    server.set_admission_handler(
        adm.admit_frame,
        probe=lambda peer: True,  # force the header shed
        shed=adm.record_shed,
    )
    client = ActorClient("127.0.0.1", server.port)
    traj = [np.ones((8, 4), np.float32)]
    client.push_trajectory(traj, [np.zeros(1, np.float32)])
    client.push_trajectory(traj, [np.zeros(1, np.float32)])
    client.close()
    deadline = time.monotonic() + 5.0
    while server.metrics()["transport_shed_frames"] < 2 and (
        time.monotonic() < deadline
    ):
        time.sleep(0.01)
    m = server.metrics()
    server.close()
    t = adm.metrics()
    assert m["transport_shed_frames"] == 2
    assert t["tenant_frames_shed"] == 2
    assert t["tenant_frames_admitted"] == 0
