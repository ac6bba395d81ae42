"""Native serving front-end tests (ISSUE 16): frame-fuzz parity with
the Python decoder, whole-batch hit byte-parity, admission-shed parity,
fault-site coverage on the native accept path, graceful fallback, and
the chaos scenario of a SIGKILL under a socket storm.

The contract under test: the C++ front-end (accept / framing / decode /
admission / whole-batch cache hits off the GIL) is BEHAVIORALLY
INDISTINGUISHABLE from the Python socketserver plane — same typed error
replies for wrecked frames, same busy shapes with retry hints, same
bytes for a cache hit at equal epoch ids — and its durability story is
the WAL's, untouched: acked ⊆ recovered across a SIGKILL mid-storm.
"""

import json
import os
import random
import selectors
import signal
import socket
import struct
import subprocess
import sys
import time

import msgpack
import pytest

from antidote_tpu import faults
from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.proto.client import AntidoteClient
from antidote_tpu.proto.codec import (
    MAX_FRAME,
    MessageCode,
    decode,
    read_frame,
)
from antidote_tpu.proto.server import ProtocolServer

_HDR = struct.Struct(">I")


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.uninstall()


def mk_cfg():
    # same shapes as test_proto/test_overload: warm XLA compile cache
    return AntidoteConfig(
        n_shards=2, max_dcs=2, ops_per_key=8, snap_versions=2,
        set_slots=8, rga_slots=16, keys_per_table=64, batch_buckets=(8, 64),
    )


def _boot(native: bool, **kw):
    node = AntidoteNode(mk_cfg())
    srv = ProtocolServer(node, port=0, native_frontend=native, **kw)
    if native and srv.native is None:
        srv.close()
        pytest.skip("native frontend unavailable (no g++/epoll)")
    return node, srv


def _raw_frame(code: int, body) -> bytes:
    payload = bytes([code]) + msgpack.packb(body, use_bin_type=True)
    return _HDR.pack(len(payload)) + payload


def _take_frames(buf: bytearray):
    """Pop every whole frame off a receive buffer, decoded."""
    while len(buf) >= 4:
        (n,) = _HDR.unpack(buf[:4])
        if len(buf) < 4 + n:
            return
        frame = bytes(buf[4:4 + n])
        del buf[:4 + n]
        yield decode(frame)


def _probe(port: int, raw: bytes, timeout: float = 10.0):
    """Send raw bytes on a fresh conn, half-close, and report the
    outcome: ("reply", frame) or ("closed", None).  Half-closing makes
    the silent-drop cases deterministic on both planes — the server
    sees EOF instead of waiting forever for the rest of a frame."""
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    s.settimeout(timeout)
    try:
        s.sendall(raw)
        s.shutdown(socket.SHUT_WR)
        try:
            return ("reply", read_frame(s))
        except (ConnectionError, OSError):
            return ("closed", None)
    finally:
        s.close()


# ---------------------------------------------------------------------------
# basic serving + observability
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_native_plane_serves_and_reports_stats():
    node, srv = _boot(True)
    c = AntidoteClient(port=srv.port)
    try:
        c.update_objects([("k", "counter_pn", "b", ("increment", 5))])
        vals, clock = c.read_objects([("k", "counter_pn", "b")],
                                     clock=None)
        # clocked read-your-writes still holds through the native accept
        vals2, _ = c.read_objects([("k", "counter_pn", "b")], clock=clock)
        assert vals2 == [5]
        st = srv.native.stats()
        assert st["accepted"] >= 1
        assert st["frames"] >= 3
        assert srv._pipeline_status()["native"]["open_conns"] >= 1
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# whole-batch hit byte parity (acceptance: native replies byte-identical
# to the Python serving path at equal epoch ids)
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_whole_batch_hit_bytes_match_python_path():
    node, srv = _boot(True, epoch_tick_ms=25)
    c = AntidoteClient(port=srv.port)
    s = None
    try:
        c.update_objects([("pk", "counter_pn", "b", ("increment", 11))])
        # let the serving epoch cover the write and the vc go quiescent:
        # with no further commits, publish keeps re-advancing the SAME
        # clock, so replies on either plane must be byte-identical
        time.sleep(0.6)
        req = _raw_frame(MessageCode.STATIC_READ_OBJECTS, {
            "objects": [["pk", "counter_pn", "b"]], "clock": None,
        })
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        s.settimeout(10)
        replies = []
        deadline = time.monotonic() + 20
        hits0 = srv.native.stats()["native_hits"]
        while srv.native.stats()["native_hits"] == hits0:
            assert time.monotonic() < deadline, \
                "native plane never served a whole-batch hit"
            s.sendall(req)
            replies.append(read_frame(s))
        s.sendall(req)  # one more, definitely native-served
        replies.append(read_frame(s))
        # the first reply crossed to Python (cold mirror); the last was
        # served by the C++ mirror — byte-identical, including the
        # msgpack map layout and the commit clock
        assert replies[-1] == replies[0], (
            "native hit bytes diverge from the Python reply:\n"
            f"  python: {replies[0]!r}\n  native: {replies[-1]!r}")
        code, body = decode(replies[-1])
        assert code == MessageCode.READ_OBJECTS_RESP
        assert body["values"] == [11]
    finally:
        if s is not None:
            s.close()
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# write invalidation: a clockless read through the native mirror shows
# every acknowledged write, the first one sent after the acknowledgement
# too (the configuration counter_pn_10k's visibility guarantee)
# ---------------------------------------------------------------------------
def test_native_mirror_invalidation_converges_and_never_overshoots():
    node, srv = _boot(True, epoch_tick_ms=25)
    c = AntidoteClient(port=srv.port)
    try:
        total = 0
        for round_ in range(8):
            total += 1
            c.update_objects(
                [("wk", "counter_pn", "b", ("increment", 1))])
            # the increment is acknowledged: the very next read shows it,
            # and no read ever shows more than the store published
            for _ in range(5):
                vals, _ = c.read_objects([("wk", "counter_pn", "b")],
                                         clock=None)
                assert vals == [total], (round_, vals, total)
            # let an epoch pass: the Python fill re-arms the mirror —
            # repeat reads between writes are what the fast path owns
            time.sleep(0.06)
            for _ in range(4):
                vals, _ = c.read_objects([("wk", "counter_pn", "b")],
                                         clock=None)
                assert vals == [total]
        # the loop must have exercised the native fast path for real
        assert srv.native.stats()["native_hits"] > 0
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# frame-fuzz parity: a seeded corpus of wrecked frames answered
# IDENTICALLY by both accept planes (same typed error or same silent
# close — the Python decoder's contract is the spec)
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_frame_fuzz_corpus_parity():
    node_n, srv_n = _boot(True)
    node_p, srv_p = _boot(False)
    cp = AntidoteClient(port=srv_p.port)
    cn = AntidoteClient(port=srv_n.port)
    try:
        for cli in (cn, cp):  # identical prefill on both nodes
            cli.update_objects(
                [("fz", "counter_pn", "b", ("increment", 3))])
        time.sleep(0.4)
        rng = random.Random(0xF00D)
        corpus = []
        # -- valid reads: served (value parity asserted below)
        corpus.append(("valid-read", _raw_frame(
            MessageCode.STATIC_READ_OBJECTS,
            {"objects": [["fz", "counter_pn", "b"]], "clock": None})))
        corpus.append(("valid-read-miss", _raw_frame(
            MessageCode.STATIC_READ_OBJECTS,
            {"objects": [["nope", "counter_pn", "b"]], "clock": None})))
        # -- counter_b frames (ISSUE 18): a valid escrow mint, then a
        #    decrement beyond rights — the typed insufficient_rights
        #    refusal (kind, detail, retry hint) must be byte-identical
        #    across both accept planes
        corpus.append(("bcounter-mint", _raw_frame(
            MessageCode.STATIC_UPDATE_OBJECTS,
            {"updates": [["bz", "counter_b", "b", ["increment", [3, 0]]]],
             "clock": None})))
        corpus.append(("bcounter-overdraw", _raw_frame(
            MessageCode.STATIC_UPDATE_OBJECTS,
            {"updates": [["bz", "counter_b", "b", ["decrement", [9, 0]]]],
             "clock": None})))
        # -- garbage msgpack bodies behind a valid header + code byte:
        #    typed ERROR_RESP (decode exception name), conn kept
        for i in range(6):
            junk = bytes(rng.randrange(256) for _ in range(
                rng.randrange(1, 40)))
            payload = bytes([MessageCode.STATIC_READ_OBJECTS]) + junk
            corpus.append((f"garbage-body-{i}",
                           _HDR.pack(len(payload)) + payload))
        # -- well-formed msgpack, wrong shape: typed ERROR_RESP too
        corpus.append(("wrong-shape", _raw_frame(
            MessageCode.STATIC_READ_OBJECTS, {"objects": 42})))
        corpus.append(("unknown-code",
                       _HDR.pack(2) + bytes([251]) + b"\xc0"))
        # -- framing violations: the Python decoder drops the conn
        #    silently (ConnectionError in read_frame_buffered) — the
        #    native plane must mirror every one of these
        corpus.append(("zero-length", _HDR.pack(0) + b"\x00"))
        corpus.append(("oversized-length", _HDR.pack(MAX_FRAME + 1)))
        corpus.append(("truncated-header", b"\x00\x00"))
        corpus.append(("empty-conn", b""))
        for i in range(4):
            n = rng.randrange(8, 200)
            sent = rng.randrange(0, n - 3)
            corpus.append((f"mid-frame-close-{i}",
                           _HDR.pack(n) + bytes(sent)))

        mismatches = []
        for name, raw in corpus:
            out_n = _probe(srv_n.port, raw)
            out_p = _probe(srv_p.port, raw)
            if out_n[0] != out_p[0]:
                mismatches.append((name, out_n[0], out_p[0]))
                continue
            if out_n[0] == "reply":
                code_n, body_n = decode(out_n[1])
                code_p, body_p = decode(out_p[1])
                if code_n != code_p:
                    mismatches.append((name, code_n, code_p))
                elif code_n == MessageCode.ERROR_RESP:
                    # typed errors must match byte-for-byte: same
                    # exception name, same detail text, same layout
                    if out_n[1] != out_p[1]:
                        mismatches.append((name, body_n, body_p))
                elif body_n.get("values") != body_p.get("values"):
                    # served reads: value parity (clocks are per-node)
                    mismatches.append(
                        (name, body_n.get("values"), body_p.get("values")))
        assert not mismatches, \
            "native/python planes diverged on:\n" + "\n".join(
                f"  {n}: native={a!r} python={b!r}"
                for n, a, b in mismatches)
    finally:
        cn.close()
        cp.close()
        srv_n.close()
        srv_p.close()


# ---------------------------------------------------------------------------
# admission-shed parity: both planes refuse with the SAME typed busy
# reply — detail string and retry hint included (acceptance criterion)
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_admission_shed_busy_reply_parity():
    caps = dict(max_in_flight=64, max_in_flight_per_client=1)

    def shed_bytes(node, srv, in_flight):
        """Wedge the commit plane, park one admitted update, and
        capture the raw busy frame a second same-host conn receives."""
        a = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        a.settimeout(30)
        b = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        b.settimeout(10)
        try:
            with node.txm.commit_lock:
                a.sendall(_raw_frame(MessageCode.STATIC_UPDATE_OBJECTS, {
                    "updates": [["sk", "counter_pn", "b",
                                 ["increment", 1]]],
                    "clock": None,
                }))
                deadline = time.monotonic() + 20
                while in_flight() < 1:  # a admitted + parked on commit
                    assert time.monotonic() < deadline
                    time.sleep(0.005)
                # same host, cold key (no fast-path hit): per-client cap
                b.sendall(_raw_frame(MessageCode.STATIC_READ_OBJECTS, {
                    "objects": [["cold", "counter_pn", "b"]],
                    "clock": None,
                }))
                busy = read_frame(b)
            ack = read_frame(a)  # the parked update completed
            code, body = decode(ack)
            assert "commit_clock" in body, body
            return busy
        finally:
            a.close()
            b.close()

    node_n, srv_n = _boot(True, **caps)
    try:
        busy_n = shed_bytes(node_n, srv_n,
                            lambda: srv_n.native.stats()["in_flight"])
        assert srv_n.native.stats()["sheds"] >= 1
    finally:
        srv_n.close()
    node_p, srv_p = _boot(False, **caps)
    try:
        busy_p = shed_bytes(node_p, srv_p, srv_p.admission.in_flight)
    finally:
        srv_p.close()

    # the C++ admission layer mirrors overload.py exactly: same error
    # kind, same human-readable detail, same pressure-scaled hint —
    # byte-for-byte, so client backoff logic cannot tell the planes apart
    assert busy_n == busy_p, (busy_n, busy_p)
    code, body = decode(busy_n)
    assert code == MessageCode.ERROR_RESP
    assert body["error"] == "busy"
    assert body["detail"] == \
        "client 127.0.0.1 at max_in_flight_per_client=1"
    assert body["retry_after_ms"] >= 25


# ---------------------------------------------------------------------------
# fallback + fault sites on the native path
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_env_kill_switch_falls_back_to_python_plane(monkeypatch):
    monkeypatch.setenv("ANTIDOTE_NATIVE_FRONTEND", "off")
    node = AntidoteNode(mk_cfg())
    srv = ProtocolServer(node, port=0, native_frontend=True)
    c = AntidoteClient(port=srv.port)
    try:
        assert srv.native is None  # the advertised port is socketserver's
        c.update_objects([("e", "counter_pn", "b", ("increment", 2))])
        vals, _ = c.read_objects([("e", "counter_pn", "b")])
        assert vals == [2]
        assert "native" not in srv._pipeline_status()
    finally:
        c.close()
        srv.close()


@pytest.mark.smoke
def test_injected_load_failure_falls_back_and_counts():
    from antidote_tpu.obs.metrics import net_metrics

    plan = faults.FaultPlan(seed=3)
    plan.error("native_frontend.load")
    faults.install(plan)
    before = net_metrics().frontend_fallback.value()
    node = AntidoteNode(mk_cfg())
    srv = ProtocolServer(node, port=0, native_frontend=True)
    c = AntidoteClient(port=srv.port)
    try:
        assert srv.native is None
        assert net_metrics().frontend_fallback.value() == before + 1
        c.update_objects([("f", "counter_pn", "b", ("increment", 1))])
        vals, _ = c.read_objects([("f", "counter_pn", "b")])
        assert vals == [1]
    finally:
        c.close()
        srv.close()


def test_frontend_recv_faults_fire_on_native_path():
    """frontend.recv drop/truncate rules are applied per drained frame
    on the native plane too — and an armed frontend.* rule disables
    fast-serve at boot, so NO frame can dodge the plan via a C++ hit."""
    plan = faults.FaultPlan(seed=11)
    plan.drop("frontend.recv", times=1)
    plan.truncate("frontend.recv", times=1, keep=5)
    inj = faults.install(plan)
    node, srv = _boot(True)
    try:
        req = _raw_frame(MessageCode.STATIC_READ_OBJECTS, {
            "objects": [["k", "counter_pn", "b"]], "clock": None,
        })
        # rule 1 (drop): the frame vanishes and the conn is closed —
        # the client sees EOF, never a hung socket
        out = _probe(srv.port, req)
        assert out[0] == "closed", out
        # rule 2 (truncate to 5 bytes): the mangled frame decodes to a
        # typed ERROR_RESP, exactly like the Python plane's twin site
        out = _probe(srv.port, req)
        assert out[0] == "reply", out
        code, body = decode(out[1])
        assert code == MessageCode.ERROR_RESP, body
        # rules exhausted: the plane serves normally again
        out = _probe(srv.port, req)
        assert out[0] == "reply" and \
            decode(out[1])[0] == MessageCode.READ_OBJECTS_RESP
        assert inj.fired("frontend.recv") == 2
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# ISSUE 28: a static read the drain thread parks carries its connection
# — no per-connection thread, the answering stage sends the reply
# ---------------------------------------------------------------------------
def _read_frame_of(key, bucket="b", **extra):
    return _raw_frame(MessageCode.STATIC_READ_OBJECTS, {
        "objects": [[key, "counter_pn", bucket]], "clock": None, **extra})


def _boot_gathering(**kw):
    """A native server on which every static read crosses to Python and
    misses the snapshot cache: the read takes the gather path."""
    kw.setdefault("max_in_flight_per_client", 256)
    node, srv = _boot(True, epoch_tick_ms=25, **kw)
    node.txm.store.snapshot_cache_cap = 0
    srv.native.set_fast_serve(False)
    return node, srv


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.002)


def _settle(node):
    """Until the published serving epoch covers every acked commit (the
    ticker covers a deferred publish within a tick)."""
    txm = node.txm
    _wait(lambda: node.store.serving_epoch is not None
          and int(node.store.serving_epoch.vc[txm.my_dc])
          >= max(txm.commit_counter, txm.epoch_lag_counter))
    time.sleep(0.05)


def _conn_threads():
    import threading

    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith("antidote-native-conn-"))


def _hold_writeback(node):
    """Hold the writeback stage inside ``epoch_read_finish`` until the
    returned event is set."""
    import threading

    gate = threading.Event()
    finish = node.txm.store.epoch_read_finish

    def held(pending):
        assert gate.wait(30), "test never released the writeback"
        return finish(pending)

    node.txm.store.epoch_read_finish = held
    return gate


@pytest.mark.smoke
def test_direct_reads_create_no_connection_thread_and_count():
    """One static read at a time per connection: served by the drain
    thread and the pipeline's stages alone; a batch's replies are one
    native send; the Python plane counts nothing."""
    import threading

    node, srv = _boot_gathering()
    w = AntidoteClient(port=srv.port)
    try:
        for i in range(12):
            w.update_objects([(f"d{i}", "counter_pn", "b",
                               ("increment", i + 1))])
        _settle(node)
        before = _conn_threads()        # the writer's worker, at most
        st0 = srv._pipeline_status()
        wrong = []

        def reader(t):
            c = AntidoteClient(port=srv.port)
            try:
                for r in range(40):
                    i = (t * 5 + r) % 12
                    vals, _ = c.read_objects([(f"d{i}", "counter_pn", "b")])
                    if vals != [i + 1]:
                        wrong.append((i, vals))
                    # no thread was made for a connection that only reads
                    assert _conn_threads() == before
            finally:
                c.close()

        ts = [threading.Thread(target=reader, args=(t,)) for t in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
            assert not t.is_alive()
        st1 = srv._pipeline_status()
    finally:
        w.close()
        srv.close()
    assert not wrong
    assert st1["direct"]["served"] - st0["direct"]["served"] == 240
    assert st1["direct"]["worker"] == st0["direct"]["worker"] == 0
    assert st1["reads"]["gather"] - st0["reads"].get("gather", 0) == 240
    n0, n1 = st0["native"], st1["native"]
    launches = (st1["stages"]["launch"]["count"]
                - st0["stages"]["launch"]["count"])
    assert n1["send_frames"] - n0["send_frames"] == 240
    # one native send a writeback batch, whatever rode in it
    assert n1["send_calls"] - n0["send_calls"] == launches
    # every stage of the record is there, reply included
    g = st1["paths"]["gather"]
    assert g["reply"]["count"] == g["total"]["count"] >= 240
    assert g["cross"]["count"] == g["decode"]["count"] == g["total"]["count"]

    # the Python plane: every work is waited on, nothing is counted
    node_p, srv_p = _boot(False)
    c = AntidoteClient(port=srv_p.port)
    try:
        c.update_objects([("p", "counter_pn", "b", ("increment", 1))])
        for _ in range(5):
            assert c.read_objects([("p", "counter_pn", "b")])[0] == [1]
        assert srv_p._pipeline_status()["direct"] == {"served": 0,
                                                      "worker": 0}
    finally:
        c.close()
        srv_p.close()


def _metric_file_and_ctx(name):
    """A status_delta metric's file, held to its BENCHMARK.json entry,
    and a maker of reader contexts from two ``pipeline`` status blocks."""
    from types import SimpleNamespace

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["source"] == "program_counter"
    for key in ("unit", "better", "layer", "moves", "workloads"):
        assert spec[key] == entry[0][key], key

    def ctx(pre, post):
        return SimpleNamespace(status={"window": (
            {"pipeline": pre}, {"pipeline": post})})

    return spec, ctx


def test_direct_share_metric_file_reads_the_counter():
    """`frontend.direct_share`: its file agrees with its BENCHMARK.json
    entry, reads the share off two node statuses, and reads nothing —
    without raising — off a program that has no such counter."""
    from benchmarks.readers import status_delta

    spec, ctx = _metric_file_and_ctx("frontend.direct_share")
    assert status_delta.read(spec, ctx(
        {"direct": {"served": 10, "worker": 30}},
        {"direct": {"served": 1000, "worker": 40}})) == pytest.approx(99.0)
    # no static read crossed in the window / the parent's status
    assert status_delta.read(spec, ctx(
        {"direct": {"served": 7, "worker": 1}},
        {"direct": {"served": 7, "worker": 1}})) is None
    assert status_delta.read(spec, ctx({}, {})) is None


def test_mirror_fill_keys_metric_file_reads_the_counters():
    """`store.mirror_fill_keys`: keys per native fill call over the
    window, off the counters a served node's status really carries;
    nothing, without raising, off the parent's status (no such pair)."""
    from benchmarks.readers import status_delta

    spec, ctx = _metric_file_and_ctx("store.mirror_fill_keys")
    assert status_delta.read(spec, ctx(
        {"native": {"fill_keys": 100, "fill_calls": 90}},
        {"native": {"fill_keys": 2100, "fill_calls": 190}})) \
        == pytest.approx(20.0)
    assert status_delta.read(spec, ctx(
        {"native": {"fill_keys": 5, "fill_calls": 5}},
        {"native": {"fill_keys": 5, "fill_calls": 5}})) is None
    parent = {"native": {"send_calls": 3, "cross_frames": 9}}
    assert status_delta.read(spec, ctx(parent, parent)) is None
    assert status_delta.read(spec, ctx({}, {})) is None
    node, srv = _boot(True)
    try:
        native = srv._pipeline_status()["native"]
        for term in spec["num"] + spec["den"]:
            assert term["path"].split(".")[-1] in native, term
    finally:
        srv.close()


#: the four metric files of cell counter_pn_10k.update_read (ISSUE 31):
#: name -> (two status blocks of a window, what the file reads of them)
_MIRROR_METRICS = {
    "frontend.mirror_hit_share": (
        {"pipeline": {"native": {"native_hits": 100},
                      "direct": {"served": 50, "worker": 10}}},
        {"pipeline": {"native": {"native_hits": 500},
                      "direct": {"served": 550, "worker": 110}}},
        40.0),
    "frontend.mirror_refused_share": (
        {"pipeline": {"native": {"fill_refused": 5, "fill_keys": 100}}},
        {"pipeline": {"native": {"fill_refused": 305, "fill_keys": 1100}}},
        30.0),
    "txn.mirror_invalidate_ms": (
        {"write_plane": {"phases": {"mirror_invalidate": {
            "sum_ms": 1.0, "count": 10}}}},
        {"write_plane": {"phases": {"mirror_invalidate": {
            "sum_ms": 13.0, "count": 90}}}},
        0.15),
    "txn.mirror_invalidate_keys": (
        {"pipeline": {"native": {"invalidate_keys": 30,
                                 "invalidate_calls": 1}}},
        {"pipeline": {"native": {"invalidate_keys": 3030,
                                 "invalidate_calls": 101}}},
        30.0),
}


@pytest.mark.parametrize("name", sorted(_MIRROR_METRICS))
def test_mirror_metric_file_reads_the_counters(name):
    """Each of the new cell's metric files agrees with its BENCHMARK.json
    entry, reads its ratio off two node statuses whose paths a served
    node's status really has, and reads nothing — without raising — when
    nothing advanced, off the parent's status (the program before the
    counters), and off the Python plane's."""
    from types import SimpleNamespace

    from benchmarks.readers import status_delta

    spec, _ = _metric_file_and_ctx(name)
    pre, post, want = _MIRROR_METRICS[name]
    assert spec["workloads"] == ["counter_pn_10k.update_read"]

    def read(a, b):
        return status_delta.read(
            spec, SimpleNamespace(status={"window": (a, b)}))

    assert read(pre, post) == pytest.approx(want)
    assert read(post, post) is None
    # the parent: a native block and phases without this PR's counters
    parent = {"pipeline": {"native": {"fill_keys": 9, "fill_calls": 3},
                           "direct": {"served": 4, "worker": 0}},
              "write_plane": {"phases": {"certify": {"sum_ms": 1.0,
                                                     "count": 2}}}}
    if name != "frontend.mirror_hit_share":    # its counters predate it
        later = {"pipeline": {"native": {"fill_keys": 99, "fill_calls": 30},
                              "direct": {"served": 40, "worker": 0}},
                 "write_plane": {"phases": {"certify": {"sum_ms": 9.0,
                                                        "count": 20}}}}
        assert read(parent, later) is None
    # the Python plane: no native block, no mirror phase counted
    assert read({"pipeline": {}, "write_plane": {"phases": {}}},
                {"pipeline": {}, "write_plane": {"phases": {}}}) is None
    node, srv = _boot(True)
    c = AntidoteClient(port=srv.port)
    try:
        c.update_objects([("mm", "counter_pn", "b", ("increment", 1))])
        st = c.node_status()
        for term in spec["num"] + spec["den"]:
            assert status_delta.lookup(st, term["path"]) is not None, term
        ph = st["write_plane"]["phases"]["mirror_invalidate"]
        assert ph["count"] == st["write_plane"]["phases"]["certify"]["count"]
        assert 0 < ph["sum_ms"] <= st["write_plane"]["phases"]["certify"][
            "sum_ms"]
    finally:
        c.close()
        srv.close()


def test_pipelined_connection_gets_replies_in_request_order():
    """read-miss, read-hit, update, read written without waiting, 200
    rounds: the first frame parks from the drain thread, the rest take
    the worker, which may not overtake it; and the next round's first
    read sees the update the round before acknowledged."""
    node, srv = _boot(True, epoch_tick_ms=25, max_in_flight_per_client=256)
    c = AntidoteClient(port=srv.port)
    s = None
    try:
        c.update_objects([("o-hit", "counter_pn", "b",
                           ("increment", 1000003))])
        c.update_objects([("o-hit2", "counter_pn", "b",
                           ("increment", 1000005))])
        time.sleep(0.3)
        for k in ("o-hit", "o-hit2"):       # into the cache and the mirror
            c.read_objects([(k, "counter_pn", "b")])
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        s.settimeout(30)
        round_ = (_read_frame_of("o-miss") + _read_frame_of("o-hit")
                  + _raw_frame(MessageCode.STATIC_UPDATE_OBJECTS, {
                      "updates": [["o-miss", "counter_pn", "b",
                                   ["increment", 1]]], "clock": None})
                  + _read_frame_of("o-hit2"))
        for r in range(200):
            s.sendall(round_)
            got = [decode(read_frame(s)) for _ in range(4)]
            assert [code for code, _ in got] == [
                MessageCode.READ_OBJECTS_RESP, MessageCode.READ_OBJECTS_RESP,
                MessageCode.COMMIT_RESP, MessageCode.READ_OBJECTS_RESP], \
                (r, got)
            assert [b.get("values") for _, b in got] == [
                [r], [1000003], None, [1000005]], (r, got)
        d = srv._pipeline_status()["direct"]
        # each round's first frame found nothing of its connection in
        # Python; the two reads behind it did
        assert d["served"] >= 200 and d["worker"] >= 400, d
    finally:
        if s is not None:
            s.close()
        c.close()
        srv.close()


@pytest.mark.parametrize("refusal", ["gate_full", "tenant_busy", "deadline"])
def test_direct_read_refusal_is_the_worker_paths_frame(refusal):
    """Two identical reads in one write: the first is parked (or
    refused) by the drain thread, the second, pipelined behind it, by
    the connection's worker.  Both are refused the same way, and the two
    error frames are the same bytes — the one mapping, ``_error_body``."""
    from antidote_tpu.overload import (BusyError, DeadlineExceeded,
                                       TenantBusyError)
    from antidote_tpu.tenancy import TenantRegistry

    kw = {}
    if refusal == "tenant_busy":
        kw["tenants"] = TenantRegistry.from_flags(["gold:1,max_in_flight=1"])
    node, srv = _boot_gathering(**kw)
    bucket = "gold/b" if refusal == "tenant_busy" else "b"
    c = AntidoteClient(port=srv.port)
    s = None
    try:
        c.update_objects([("rf", "counter_pn", bucket, ("increment", 2))])
        _settle(node)
        extra = {}
        if refusal == "gate_full":
            srv._static_q.lane_caps = dict.fromkeys(
                srv._static_q.lane_caps, 0)
            want, kind = BusyError, "busy"
        elif refusal == "tenant_busy":
            srv.admission.tenant_enter("gold")      # its one slot is taken
            want, kind = TenantBusyError, "tenant_busy"
        else:
            extra["deadline_ms"] = 0.001            # passes while parked
            want, kind = DeadlineExceeded, "deadline"
        d0 = dict(srv._direct)
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        s.settimeout(20)
        s.sendall(_read_frame_of("rf", bucket, **extra) * 2)
        first, second = read_frame(s), read_frame(s)
        assert {k: srv._direct[k] - d0[k] for k in d0} == {
            "served": 1, "worker": 1}
        assert first == second, (first, second)
        code, body = decode(first)
        assert code == MessageCode.ERROR_RESP and body["error"] == kind
        # ... and it is what the mapping makes of that exception
        if want is DeadlineExceeded:
            e = want(body["detail"])
        elif want is BusyError:
            e = want(body["detail"], retry_after_ms=body["retry_after_ms"])
        else:
            e = want(body["detail"], tenant="gold",
                     retry_after_ms=body["retry_after_ms"])
        assert srv._error_body(e) == body
        # nothing stays accounted: slots, tenant account, stage records
        _wait(lambda: srv.native.stats()["in_flight"] == 0)
        assert srv.admission.tenant_in_flight("default") == 0
        assert srv.admission.tenant_in_flight("gold") == (
            1 if refusal == "tenant_busy" else 0)
        # (a refusal at the tenant's account comes before the work is
        # the request's: its record closes under "other")
        _wait(lambda: sum(
            p["total"]["count"]
            for name, p in srv._pipeline_status()["paths"].items()
            if name in ("shed", "other")) == 2)
    finally:
        if s is not None:
            s.close()
        c.close()
        srv.close()


def test_connection_dropped_with_its_direct_read_in_the_pipeline():
    """The reply has nowhere to go, but what the read holds is given
    back: the admission slot (global and per host), the tenant account,
    the writeback slot — the next connection of that host is served."""
    node, srv = _boot_gathering(max_in_flight=64,
                                max_in_flight_per_client=1)
    c = AntidoteClient(port=srv.port)
    try:
        c.update_objects([("dr", "counter_pn", "b", ("increment", 9))])
        _settle(node)
        threads0 = _conn_threads()                   # c's worker
        gate = _hold_writeback(node)
        seq0 = srv._launch_seq
        closed0 = srv.native.stats()["closed"]
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        s.sendall(_read_frame_of("dr"))
        _wait(lambda: srv._launch_seq == seq0 + 1)   # launched, held
        assert srv.native.stats()["in_flight"] == 1
        assert srv.admission.tenant_in_flight("default") == 1
        # a reset, not a half-close (after which the reply is still owed)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        s.close()
        _wait(lambda: srv.native.stats()["closed"] == closed0 + 1)
        assert srv.native.stats()["in_flight"] == 1  # still owed a send
        gate.set()
        _wait(lambda: srv.native.stats()["in_flight"] == 0)
        _wait(lambda: srv.admission.tenant_in_flight("default") == 0)
        _wait(lambda: srv._wb_unfinished == 0)
        assert _conn_threads() == threads0
        # the host's one slot came back (max_in_flight_per_client=1)
        assert c.read_objects([("dr", "counter_pn", "b")])[0] == [9]
        assert srv.native.stats()["sheds"] == 0
    finally:
        c.close()
        srv.close()


def test_send_many_is_n_sends():
    """frontend_send_many of n frames against n frontend_send calls: the
    sockets get the same bytes, the front end's counters end equal
    (``send_calls`` apart, which is the point)."""
    from antidote_tpu.proto.native_frontend import NativeFrontend

    n = 5

    def run(many: bool):
        nf = NativeFrontend.create("127.0.0.1", 0, 64, 32, 32)
        if nf is None:
            pytest.skip("native frontend unavailable (no g++/epoll)")
        socks = []
        try:
            for i in range(n):
                s = socket.create_connection(("127.0.0.1", nf.port),
                                             timeout=10)
                s.settimeout(10)
                # an update crosses to Python whatever the mirror holds
                s.sendall(_raw_frame(MessageCode.STATIC_UPDATE_OBJECTS,
                                     {"i": i}))
                socks.append(s)
            frames = []
            deadline = time.monotonic() + 10
            while len(frames) < n:
                assert time.monotonic() < deadline
                frames += nf.take_batch(200)
            assert all(k == nf.K_FRAME and aux == 0
                       for _c, k, aux, _p, _t in frames)
            by_i = {msgpack.unpackb(p[1:])["i"]: cid
                    for cid, _k, _a, p, _t in frames}
            assert nf.stats()["in_flight"] == n
            replies = [(by_i[i], _raw_frame(MessageCode.ERROR_RESP,
                                            {"n": i, "pad": "x" * (7 * i)}),
                        1) for i in range(n)]
            # one reply of a batch may be the account-only empty one
            replies[2] = (replies[2][0], b"", 1)
            calls0 = nf.stats()["send_calls"]
            if many:
                nf.send_many(replies)
            else:
                for r in replies:
                    nf.send(*r)
            got = [read_frame(s) if i != 2 else None
                   for i, s in enumerate(socks)]
            _wait(lambda: nf.stats()["send_frames"] == n - 1)
            st = nf.stats()
            return got, st, st["send_calls"] - calls0
        finally:
            for s in socks:
                s.close()
            nf.close()

    got_m, st_m, calls_m = run(True)
    got_s, st_s, calls_s = run(False)
    assert got_m == got_s and got_m[0] is not None
    assert (calls_m, calls_s) == (1, n)
    for k in ("in_flight", "send_frames", "forwarded", "frames",
              "cross_frames", "sheds", "open_conns"):
        assert st_m[k] == st_s[k], k
    assert st_m["in_flight"] == 0


def _bare_frontend(mirror_cap=None):
    """A front end with no server behind it (its mirror is filled by
    hand); ``mirror_cap`` shrinks the mirror for the eviction case."""
    from antidote_tpu.proto import native_frontend as nfm

    if mirror_cap is None:
        nf = nfm.NativeFrontend.create("127.0.0.1", 0, 64, 32, 32)
    else:
        lib = nfm._load_lib()
        h = lib and lib.frontend_create(b"127.0.0.1", 0, 64, 32, 32,
                                        mirror_cap)
        nf = nfm.NativeFrontend(lib, h) if h else None
    if nf is None:
        pytest.skip("native frontend unavailable (no g++/epoll)")
    return nf


def test_fill_many_is_n_fills():
    """frontend_fill_many of N entries against N frontend_fill calls: the
    mirror ends the same — every key a native hit whose reply is the
    Python plane's, byte for byte — an entry that does not pack is
    skipped alone, and the counters say one call, N keys."""
    from antidote_tpu.proto.codec import encode, encode_value

    epoch, vc = 7, [3, 0]
    entries = [
        ("fk0", "b", "counter_pn", 41),
        ("fk1", "b", "set_aw", ["a", "b", "c"]),
        (object(), "b", "counter_pn", 1),         # key does not pack
        ("fk2", "b2", "register_lww", "v"),
        ("fk3", "b", "counter_pn", object()),     # value does not pack
        ("fk4", "b", "map_rr", {("f", "counter_pn"): 2}),
        (("t", 1), "b", "counter_pn", -5),        # a tuple key packs
    ]
    good = [e for e in entries if e[0].__class__ is not object
            and e[3].__class__ is not object]
    assert len(good) == len(entries) - 2

    def run(many: bool):
        nf = _bare_frontend()
        s = None
        try:
            nf.advance(epoch, vc, True)
            st0 = nf.stats()
            if many:
                nf.fill_many(entries, epoch)
            else:
                for key, bucket, tn, v in entries:
                    nf.fill(key, bucket, tn, v, epoch)
            st1 = nf.stats()
            s = socket.create_connection(("127.0.0.1", nf.port),
                                         timeout=10)
            s.settimeout(10)
            got = []
            for key, bucket, tn, _v in good:
                s.sendall(_raw_frame(MessageCode.STATIC_READ_OBJECTS, {
                    "objects": [[key, tn, bucket]], "clock": None}))
                got.append(read_frame(s))
            st2 = nf.stats()
            assert st2["native_hits"] - st1["native_hits"] == len(good)
            assert st2["forwarded"] == 0
            return got, {k: st1[k] - st0[k] for k in st1}
        finally:
            if s is not None:
                s.close()
            nf.close()

    got_m, d_m = run(True)
    got_s, d_s = run(False)
    assert got_m == got_s
    for (key, bucket, tn, v), frame in zip(good, got_m):
        assert frame == encode(MessageCode.READ_OBJECTS_RESP, {
            "values": [encode_value(v)], "commit_clock": vc})[4:], key
    n = len(good)
    assert (d_m["fill_calls"], d_m["fill_keys"]) == (1, n)
    assert (d_s["fill_calls"], d_s["fill_keys"]) == (n, n)
    assert d_m["mirror_size"] == d_s["mirror_size"] == n


def test_fill_many_nothing_packs_makes_no_call():
    nf = _bare_frontend()
    try:
        nf.advance(1, [0, 0], True)
        nf.fill_many([(object(), "b", "counter_pn", 1)], 1)
        nf.fill_many([], 1)
        st = nf.stats()
        assert (st["fill_calls"], st["fill_keys"], st["mirror_size"]) \
            == (0, 0, 0)
    finally:
        nf.close()


def test_fill_many_is_one_step_under_hits_and_other_fillers():
    """Stress, time-bounded: two threads fill the same 16 keys batch by
    batch (a batch carries one value) while two connections read the
    first and the last key in one request.  A batch goes in under one
    take of the front end's mutex and a hit is built under it, so both
    values of every reply come from one batch; the calls keep the GIL
    across that mutex and nothing hangs."""
    import sys
    import threading

    nf = _bare_frontend()
    keys = [f"sk{i}" for i in range(16)]
    stop = threading.Event()
    errors = []
    replies = [0, 0]

    def filler(base):
        n = base
        while not stop.is_set():
            nf.fill_many([(k, "b", "counter_pn", n) for k in keys], 3)
            n += 2

    def reader(slot):
        try:
            s = socket.create_connection(("127.0.0.1", nf.port), timeout=10)
            s.settimeout(10)
            req = _raw_frame(MessageCode.STATIC_READ_OBJECTS, {
                "objects": [[keys[0], "counter_pn", "b"],
                            [keys[-1], "counter_pn", "b"]], "clock": None})
            try:
                while not stop.is_set():
                    s.sendall(req)
                    a, b = decode(read_frame(s))[1]["values"]
                    if a != b:
                        errors.append((a, b))
                    replies[slot] += 1
            finally:
                s.close()
        except BaseException as e:  # noqa: BLE001 — the test reads it
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        nf.advance(3, [1, 0], True)
        nf.fill_many([(k, "b", "counter_pn", 0) for k in keys], 3)
        threads = [threading.Thread(target=filler, args=(i,), daemon=True)
                   for i in (1, 2)]
        threads += [threading.Thread(target=reader, args=(i,), daemon=True)
                    for i in (0, 1)]
        for t in threads:
            t.start()
        time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(10)
            assert not t.is_alive()
        st = nf.stats()
    finally:
        sys.setswitchinterval(interval)
        nf.close()
    assert not errors, errors[:3]
    assert min(replies) > 50
    assert st["forwarded"] == 0 and st["native_hits"] == sum(replies)
    assert st["fill_keys"] == 16 * st["fill_calls"]
    assert st["mirror_size"] == 16


@pytest.mark.parametrize("many", [True, False], ids=["fill_many", "fill"])
def test_mirror_cap_evicts_on_fill(many):
    """Past the cap every new key evicts one entry (an arbitrary one);
    a key the mirror already holds is rewritten in place."""
    cap = 4
    nf = _bare_frontend(mirror_cap=cap)
    try:
        nf.advance(2, [0, 0], True)
        entries = [(f"ck{i}", "b", "counter_pn", i) for i in range(10)]
        # a rewrite of a held key at the cap evicts nothing
        tail = [("ck9", "b", "counter_pn", 99)]
        if many:
            nf.fill_many(entries, 2)
            nf.fill_many(tail, 2)
        else:
            for key, bucket, tn, v in entries + tail:
                nf.fill(key, bucket, tn, v, 2)
        st = nf.stats()
        assert st["mirror_size"] == cap
        assert st["fill_keys"] == 11
        assert st["fill_calls"] == (2 if many else 11)
        s = socket.create_connection(("127.0.0.1", nf.port), timeout=10)
        s.settimeout(10)
        try:
            # the last key filled is held, with its rewritten value
            s.sendall(_raw_frame(MessageCode.STATIC_READ_OBJECTS, {
                "objects": [["ck9", "counter_pn", "b"]], "clock": None}))
            assert decode(read_frame(s))[1]["values"] == [99]
        finally:
            s.close()
    finally:
        nf.close()


def _native_read(port, key):
    """One clockless read of ``key`` on a fresh connection: the value."""
    s = socket.create_connection(("127.0.0.1", port), timeout=10)
    s.settimeout(20)
    try:
        s.sendall(_read_frame_of(key))
        code, body = decode(read_frame(s))
        assert code == MessageCode.READ_OBJECTS_RESP
        return body["values"][0]
    finally:
        s.close()


@pytest.mark.parametrize("held", [True, False],
                         ids=["entry", "taught_bottom"])
def test_mirror_refuses_a_fill_read_before_an_invalidation(held):
    """The mirror's rule (frontend.cc), step by step on a booted server's
    mirror, its ticker far away: at epoch E a key is invalidated (a commit
    under the lock), then a value read before that arrives — a gather
    launched at E whose writeback calls ``fill_many``, or the bottom that
    ``epoch_cache_read`` teaches for a key the mirror has no entry of.
    The mirror turns it away: once the commit is acknowledged no clockless
    read is a native hit carrying it, at E or after the advance to E+1;
    a value read at E+1 is taken, one stamped E no longer."""
    node, srv = _boot(True, epoch_tick_ms=3_600_000)
    nf = srv.native
    key, stale, fresh = "mk", 41, 42      # the store itself holds 0
    E = 1000
    try:
        nf.advance(E, [5, 0], True)
        if held:
            nf.fill(key, "b", "counter_pn", stale, E)
            assert _native_read(srv.port, key) == stale     # a hit
        st0 = nf.stats()
        nf.invalidate(key, "b")         # the commit, under its lock
        if held:                        # the late writeback of the gather
            nf.fill_many([(key, "b", "counter_pn", stale)], E)
        else:                           # the late taught bottom
            nf.fill(key, "b", "counter_pn", stale, E)
        # ... the commit publishes E+1 and is acknowledged
        assert _native_read(srv.port, key) != stale
        assert nf.stats()["mirror_marks"] == 1
        nf.advance(E + 1, [6, 0], True)
        assert _native_read(srv.port, key) != stale
        st1 = nf.stats()
        assert st1["native_hits"] == st0["native_hits"]
        assert st1["fill_refused"] - st0["fill_refused"] == 1
        assert st1["fill_keys"] - st0["fill_keys"] == 1
        assert (st1["invalidate_calls"] - st0["invalidate_calls"],
                st1["invalidate_keys"] - st0["invalidate_keys"]) == (1, 1)
        assert st1["mirror_marks"] == 0         # dropped at the advance
        # a fill that arrives with the epoch left behind: refused by its
        # stamp; one read at the epoch served: taken, and served
        nf.fill_many([(key, "b", "counter_pn", stale)], E)
        assert _native_read(srv.port, key) != stale
        nf.fill_many([(key, "b", "counter_pn", fresh)], E + 1)
        assert _native_read(srv.port, key) == fresh
        st2 = nf.stats()
        assert st2["fill_refused"] - st1["fill_refused"] == 1
        assert st2["native_hits"] - st1["native_hits"] == 1
        # and in the node status, where the benchmark reads them
        native = srv._pipeline_status()["native"]
        assert {"fill_refused", "invalidate_calls", "invalidate_keys",
                "mirror_marks"} <= set(native)
    finally:
        srv.close()


def test_mirror_marks_are_bounded_by_the_mirror_cap():
    """More keys invalidated in one epoch than the mirror may hold: the
    marks collapse into 'refuse every fill' until the next advance."""
    nf = _bare_frontend(mirror_cap=4)
    try:
        nf.advance(1, [0, 0], True)
        nf.invalidate_many([(f"bk{i}", "b") for i in range(6)])
        assert nf.stats()["mirror_marks"] == 4
        nf.fill("other", "b", "counter_pn", 1, 1)
        st = nf.stats()
        assert (st["fill_refused"], st["mirror_size"]) == (1, 0)
        nf.advance(2, [0, 0], True)
        nf.fill("other", "b", "counter_pn", 1, 2)
        st = nf.stats()
        assert (st["fill_refused"], st["mirror_size"],
                st["mirror_marks"]) == (1, 1, 0)
    finally:
        nf.close()


def test_direct_read_rerouted_to_the_locked_plane_is_answered():
    """No serving epoch to pin: the dispatcher hands the parked read to
    the locked plane, whose worker answers it — by sending, since
    nobody waits on it."""
    node, srv = _boot_gathering()
    c = AntidoteClient(port=srv.port)
    s = None
    try:
        c.update_objects([("lk", "counter_pn", "b", ("increment", 4))])
        _settle(node)
        node.txm.store.pin_serving_epoch = lambda: None
        st0 = srv._pipeline_status()
        threads0 = _conn_threads()
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        s.settimeout(20)
        for _ in range(3):
            s.sendall(_read_frame_of("lk"))
            code, body = decode(read_frame(s))
            assert code == MessageCode.READ_OBJECTS_RESP
            assert body["values"] == [4]
        st1 = srv._pipeline_status()
        assert st1["reads"]["locked"] - st0["reads"].get("locked", 0) == 3
        assert st1["direct"]["served"] - st0["direct"]["served"] == 3
        assert st1["paths"]["locked"]["total"]["count"] >= 3
        assert _conn_threads() == threads0
        _wait(lambda: srv.native.stats()["in_flight"] == 0)
        assert srv.admission.tenant_in_flight("default") == 0
    finally:
        if s is not None:
            s.close()
        c.close()
        srv.close()


def test_shutdown_answers_parked_direct_reads_typed():
    """close() with direct reads in the pipeline: what is parked at the
    gate is failed with a typed error frame, what was launched is still
    answered — no connection is left in silence."""
    import threading

    node, srv = _boot_gathering()
    depth = ProtocolServer.DEPTH
    c = AntidoteClient(port=srv.port)
    socks = []
    try:
        for i in range(depth + 2):
            c.update_objects([(f"sd{i}", "counter_pn", "b",
                               ("increment", i + 1))])
        _settle(node)
        c.close()
        gate = _hold_writeback(node)
        seq0 = srv._launch_seq
        for i in range(depth + 2):
            s = socket.create_connection(("127.0.0.1", srv.port),
                                         timeout=10)
            s.settimeout(20)
            s.sendall(_read_frame_of(f"sd{i}"))
            socks.append(s)
            if i < depth:       # one launch each: every slot is taken
                _wait(lambda: srv._launch_seq == seq0 + i + 1)
        _wait(lambda: srv.admission.tenant_in_flight("default")
              == depth + 2)
        time.sleep(0.05)
        closer = threading.Thread(target=srv.close, daemon=True)
        closer.start()
        for s in socks[depth:]:
            code, body = decode(read_frame(s))
            assert code == MessageCode.ERROR_RESP
            assert body == {"error": "ConnectionError",
                            "detail": "server shutting down"}
        gate.set()
        for i, s in enumerate(socks[:depth]):
            code, body = decode(read_frame(s))
            assert code == MessageCode.READ_OBJECTS_RESP
            assert body["values"] == [i + 1]
        closer.join(20)
        assert not closer.is_alive()
        assert srv.admission.tenant_in_flight("default") == 0
    finally:
        node.txm.store.__dict__.pop("epoch_read_finish", None)
        for s in socks:
            s.close()
        srv.close()


def test_clockless_direct_read_sees_every_acknowledged_commit():
    """The direct path skips threads, not checks: a read sent on a fresh
    connection after a commit's acknowledgement shows that commit."""
    node, srv = _boot(True, epoch_tick_ms=25, max_in_flight_per_client=256)
    w = AntidoteClient(port=srv.port)
    r = AntidoteClient(port=srv.port)
    try:
        threads0 = None
        for n in range(1, 61):
            w.update_objects([("ack", "counter_pn", "b", ("increment", 1))])
            threads0 = threads0 or _conn_threads()      # w's worker
            vals, _ = r.read_objects([("ack", "counter_pn", "b")],
                                     clock=None)
            assert vals == [n]
        d = srv._pipeline_status()["direct"]
        st = srv.native.stats()
        # r's reads were served natively or by the drain thread: it
        # never had a worker
        assert d["worker"] == 0 and d["served"] + st["native_hits"] >= 60
        assert _conn_threads() == threads0
    finally:
        w.close()
        r.close()
        srv.close()


# ---------------------------------------------------------------------------
# chaos acceptance: SIGKILL under a >=1k-socket storm with seeded
# drop/truncate faults on the native accept path — every ack made it to
# the WAL (acked ⊆ recovered), and no connection wedges.  Its fault-free
# case first holds the accept plane's own property: every one of the
# sockets, all attached at once, is answered
# ---------------------------------------------------------------------------
def _every_socket_answered(port, n_socks, n_keys):
    """``n_socks`` connections at once, one clockless static read at a
    time on each until it has two answers: no socket stays silent,
    nothing but a read reply or a typed busy comes back, and the native
    plane, where it serves, holds them all and answers hits itself."""
    c = AntidoteClient(port=port)
    socks = []
    try:
        c.update_objects([(f"r{k}", "counter_pn", "b", ("increment", k + 1))
                          for k in range(n_keys)])

        def read_frame_for(k):
            return _raw_frame(MessageCode.STATIC_READ_OBJECTS, {
                "objects": [[f"r{k}", "counter_pn", "b"]], "clock": None})

        sel = selectors.DefaultSelector()
        for i in range(n_socks):
            s = socket.create_connection(("127.0.0.1", port), timeout=30)
            s.settimeout(None)
            socks.append(s)
            # state: [rxbuf, key, read replies]
            sel.register(s, selectors.EVENT_READ, [bytearray(), i % n_keys, 0])
        native = c.node_status()["pipeline"].get("native")
        hits0 = 0 if native is None else native["native_hits"]
        if native is not None:
            assert native["open_conns"] >= n_socks, native
        for s in socks:
            s.sendall(read_frame_for(sel.get_key(s).data[1]))
        unanswered = n_socks
        deadline = time.monotonic() + 120
        while unanswered:
            assert time.monotonic() < deadline, \
                f"{unanswered}/{n_socks} sockets never got two answers"
            for sk, _ in sel.select(timeout=0.2):
                st = sk.data
                data = sk.fileobj.recv(1 << 16)
                assert data, "the server closed a storm connection"
                st[0] += data
                for code, body in _take_frames(st[0]):
                    if code == MessageCode.READ_OBJECTS_RESP:
                        assert body["values"] == [st[1] + 1], body
                        st[2] += 1
                        if st[2] == 2:
                            unanswered -= 1
                            continue
                    else:  # the admission cap against 1k closed loops
                        assert code == MessageCode.ERROR_RESP, body
                        assert body["error"] == "busy", body
                        assert body["retry_after_ms"] > 0, body
                    sk.fileobj.sendall(read_frame_for(st[1]))
        if native is not None:
            native = c.node_status()["pipeline"]["native"]
            assert native["native_hits"] > hits0, native
    finally:
        for s in socks:
            s.close()
        c.close()


@pytest.mark.parametrize("faulted", [True, False],
                         ids=["seeded_faults", "every_socket_answered"])
def test_sigkill_under_socket_storm_acked_subset_recovered(tmp_path, faulted):
    n_socks = 1024
    n_keys = 128  # sockets share keys: per-key acked sums stay testable
    log_dir = str(tmp_path / "wal")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if faulted:
        # seeded frame wreckage on the accept path for the whole run:
        # drops close conns mid-storm, truncates produce typed errors
        env["ANTIDOTE_FAULT_PLAN"] = json.dumps({"seed": 23, "rules": [
            {"site": "frontend.recv", "action": "drop", "p": 0.002,
             "times": 64},
            {"site": "frontend.recv", "action": "truncate", "p": 0.002,
             "times": 64, "arg": 6},
        ]})
    proc = subprocess.Popen(
        [sys.executable, "-m", "antidote_tpu.console", "serve",
         "--port", "0", "--shards", "2", "--max-dcs", "2",
         "--keys-per-table", "1024", "--log-dir", log_dir, "--sync-log",
         "--wal-segments", "3", "--max-connections", str(n_socks + 64),
         "--max-in-flight-per-client", "512"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True,
    )
    acked = [0] * n_keys
    attempted = [0] * n_keys
    socks = []
    try:
        info = json.loads(proc.stdout.readline())
        assert info["ready"] is True
        port = info["port"]
        if not faulted:
            _every_socket_answered(port, n_socks, n_keys)

        def upd_frame(key_i):
            return _raw_frame(MessageCode.STATIC_UPDATE_OBJECTS, {
                "updates": [[f"s{key_i}", "counter_pn", "b",
                             ["increment", 1]]],
                "clock": None,
            })

        sel = selectors.DefaultSelector()
        deadline = time.monotonic() + 60
        for i in range(n_socks):
            s = socket.create_connection(("127.0.0.1", port), timeout=30)
            s.settimeout(None)
            socks.append(s)
            key_i = i % n_keys
            # state: [rxbuf, key_i, live]
            sel.register(s, selectors.EVENT_READ, [bytearray(), key_i, True])
            attempted[key_i] += 1
            s.sendall(upd_frame(key_i))
            assert time.monotonic() < deadline, \
                f"storm connect stalled at {i} sockets"

        # closed-loop storm: each ack (commit_clock reply) immediately
        # launches the next increment on that socket; busy sheds and
        # typed errors relaunch too (refused work was NOT applied)
        t_end = time.monotonic() + 6.0
        while time.monotonic() < t_end and sum(acked) < 4000:
            for sk, _ in sel.select(timeout=0.2):
                st = sk.data
                try:
                    data = sk.fileobj.recv(1 << 16)
                except OSError:
                    data = b""
                if not data:  # fault-dropped conn: dead, not wedged
                    sel.unregister(sk.fileobj)
                    st[2] = False
                    continue
                st[0] += data
                for code, body in _take_frames(st[0]):
                    if code != MessageCode.ERROR_RESP:
                        assert "commit_clock" in body, body
                        acked[st[1]] += 1
                    attempted[st[1]] += 1
                    try:
                        sk.fileobj.sendall(upd_frame(st[1]))
                    except OSError:
                        sel.unregister(sk.fileobj)
                        st[2] = False
                        break
        assert sum(acked) >= 500, \
            f"storm never reached real throughput: {sum(acked)} acks"
        proc.send_signal(signal.SIGKILL)  # mid-storm, no goodbyes
        proc.wait(timeout=10)
        # no wedged conns: the kill severs EVERY remaining socket — each
        # one must observe EOF/reset promptly, none parks forever
        eof_deadline = time.monotonic() + 15
        live = [s for s in socks if not s._closed]
        for s in live:
            s.settimeout(max(0.1, eof_deadline - time.monotonic()))
            try:
                while s.recv(1 << 16):
                    pass
            except socket.timeout:
                pytest.fail("a connection wedged past the server's death")
            except (ConnectionError, OSError):
                pass  # reset counts as closed, same as EOF
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
    # recover twice, independently — acked ⊆ recovered ⊆ attempted per
    # key, and both recoveries are byte-identical (the WAL contract is
    # untouched by WHICH plane accepted the bytes)
    rcfg = AntidoteConfig(n_shards=2, max_dcs=2, keys_per_table=1024,
                          wal_segments=3)
    objs = [(f"s{i}", "counter_pn", "b") for i in range(n_keys)]
    recovered = []
    for _ in range(2):
        node = AntidoteNode(rcfg, log_dir=log_dir, recover=True)
        vals, _ = node.read_objects(objs)
        recovered.append(vals)
        node.store.log.close()
    assert recovered[0] == recovered[1], "recoveries diverged"
    for i in range(n_keys):
        assert acked[i] <= recovered[0][i] <= attempted[i], (
            f"s{i}: acked={acked[i]} recovered={recovered[0][i]} "
            f"attempted={attempted[i]}")
