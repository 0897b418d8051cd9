"""HTTP gateway (adam_tpu/gateway; docs/SERVING.md): wire-protocol
units and fuzz, idempotency-keyed submission (across gateway restarts
too), typed 429/503 back-pressure honored by the client policy,
cursor-resumable event streaming, Range-resumable sha256-verified part
fetch, and the two-client/two-tenant end-to-end run byte-compared to
solo runs.

Most tests ride a stub transform (timing-free); the end-to-end and
SIGTERM tests drive the REAL streamed pipeline on the numpy backend
over real sockets — the gateway's core contract is that the wire
changes how work is asked for, never the bytes."""

import hashlib
import json
import http.client
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from adam_tpu.api.transform_service import TransformService
from adam_tpu.gateway import protocol
from adam_tpu.gateway.client import (
    GatewayBusy,
    GatewayClient,
    GatewayError,
    resolve_url,
)
from adam_tpu.gateway.server import GatewayServer
from adam_tpu.serve import scheduler as sched_mod
from adam_tpu.serve.job import JobSpec
from adam_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HB = "adam_tpu.heartbeat/7"


def _parts_hash(d):
    return {
        f: hashlib.sha256(
            open(os.path.join(d, f), "rb").read()
        ).hexdigest()
        for f in sorted(os.listdir(d)) if f.startswith("part-")
    }


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


# ---------------------------------------------------------------------------
# Protocol units
# ---------------------------------------------------------------------------
def test_parse_listen():
    assert protocol.parse_listen("127.0.0.1:0") == ("127.0.0.1", 0)
    assert protocol.parse_listen("0.0.0.0:8765") == ("0.0.0.0", 8765)
    for bad in ("", "8765", "host:", "host:x", "host:70000"):
        with pytest.raises(ValueError):
            protocol.parse_listen(bad)


def test_parse_range():
    assert protocol.parse_range(None, 100) is None
    assert protocol.parse_range("bytes=0-", 100) == (0, 99)
    assert protocol.parse_range("bytes=10-19", 100) == (10, 19)
    assert protocol.parse_range("bytes=90-500", 100) == (90, 99)
    assert protocol.parse_range("bytes=-25", 100) == (75, 99)
    for bad in ("bytes=100-", "bytes=5-2", "bytes=-0", "bytes=",
                "octets=1-2", "bytes=1-2,5-6"):
        with pytest.raises(protocol.RangeError):
            protocol.parse_range(bad, 100)


def test_retry_after_from_grants():
    # cold service: conservative default
    assert protocol.retry_after_s([]) == 2
    assert protocol.retry_after_s([5.0]) == 2
    # fast cadence (0.1 s/window): ~8 windows, floored at 1 s
    fast = [i * 0.1 for i in range(20)]
    assert protocol.retry_after_s(fast, now=2.0) == 1
    # slow cadence (2 s/window): 8 windows = 16 s
    slow = [i * 2.0 for i in range(20)]
    assert protocol.retry_after_s(slow, now=38.5) == 16
    # a stalled pool decays toward the cap instead of advertising its
    # last healthy cadence forever
    stalled = [i * 0.1 for i in range(3)]
    assert protocol.retry_after_s(stalled, now=1000.0) == \
        protocol.RETRY_AFTER_MAX_S
    lo, hi = protocol.RETRY_AFTER_MIN_S, protocol.RETRY_AFTER_MAX_S
    for times in (fast, slow, stalled):
        assert lo <= protocol.retry_after_s(times, now=50.0) <= hi


def test_part_name_ok():
    assert protocol.part_name_ok("part-r-00000.parquet")
    assert protocol.part_name_ok("part-realigned.parquet")
    for bad in ("", "x.parquet", "part-", "part-a/b", "part-..",
                "part-a..b", "_metadata", ".part-hidden"):
        assert not protocol.part_name_ok(bad), bad


# ---------------------------------------------------------------------------
# Stub-backed gateway fixture
# ---------------------------------------------------------------------------
@pytest.fixture()
def stub_transform(monkeypatch):
    """Gate-controlled streamed-pipeline stub (timing-free admission
    tests; the test_serve.py idiom)."""
    release = threading.Event()

    def fake(inp, out, **kw):
        assert release.wait(30), "stub never released"
        return {"n_reads": 0, "windows_fresh": 0}

    monkeypatch.setattr(sched_mod.streamed_mod, "transform_streamed",
                        fake)
    return {"release": release}


@pytest.fixture()
def gateway(tmp_path):
    """One service + gateway + typed client on a real socket."""
    svc = TransformService(str(tmp_path / "root"), max_jobs=1)
    gw = GatewayServer(svc)
    gw.start()
    client = GatewayClient(gw.url)
    yield {"svc": svc, "gw": gw, "client": client,
           "root": str(tmp_path / "root"), "tmp": tmp_path}
    gw.close()
    svc.close()


def _doc(tmp_path, jid, **kw):
    d = {"input": "in.sam", "output": str(tmp_path / f"{jid}.adam")}
    d.update(kw)
    return d


# ---------------------------------------------------------------------------
# Submission: idempotency key, conflict, duplicate-safe retries
# ---------------------------------------------------------------------------
def test_submit_idempotent_and_conflict(gateway, stub_transform):
    c = gateway["client"]
    tmp = gateway["tmp"]
    got = c.submit("j1", _doc(tmp, "j1"))
    # the reply also echoes the minted trace_id (docs/OBSERVABILITY.md)
    assert got.pop("trace_id")
    assert got == {"job_id": "j1", "state": "pending"}
    # identical re-PUT (a client retry whose first response was lost):
    # success, carrying the job's current state
    again = c.submit("j1", _doc(tmp, "j1"))
    assert again["duplicate"] is True
    assert again["state"] in ("pending", "running")
    # same id, different spec: 409, never a silent overwrite
    with pytest.raises(GatewayError) as ei:
        c.submit("j1", _doc(tmp, "j1", window_reads=1024))
    assert ei.value.status == 409 and ei.value.kind == "conflict"
    # body job_id contradicting the path is malformed
    with pytest.raises(GatewayError) as ei:
        c.submit("j1", dict(_doc(tmp, "j1"), job_id="other"))
    assert ei.value.status == 400
    stub_transform["release"].set()
    assert gateway["svc"].wait(timeout=30)
    done = c.submit("j1", _doc(tmp, "j1"))
    assert done["duplicate"] is True and done["state"] == "done"
    # gateway.requests / request.seconds accounted (the serve ctor
    # keeps the global tracer recording)
    from adam_tpu.utils import telemetry as tele

    snap = tele.TRACE.snapshot()
    assert snap["counters"].get(tele.C_GW_REQUESTS, 0) > 0
    assert snap["histograms"][tele.H_GW_REQUEST_SECONDS]["count"] > 0


def test_idempotent_resubmission_across_gateway_restart(
    tmp_path, stub_transform,
):
    root = str(tmp_path / "root")
    svc = TransformService(root, max_jobs=1)
    gw = GatewayServer(svc)
    gw.start()
    c = GatewayClient(gw.url)
    doc = _doc(tmp_path, "r1")
    assert c.submit("r1", doc)["state"] == "pending"
    stub_transform["release"].set()
    assert svc.wait(timeout=30)
    gw.close()
    svc.close()
    # the whole process "restarts": a fresh service recovers the
    # durable JOB.json records, a fresh gateway binds a fresh port —
    # and the client's blind re-PUT is still duplicate-safe
    svc2 = TransformService(root, max_jobs=1)
    svc2.recover()
    gw2 = GatewayServer(svc2)
    gw2.start()
    try:
        c2 = GatewayClient(gw2.url)
        again = c2.submit("r1", doc)
        assert again["duplicate"] is True and again["state"] == "done"
        with pytest.raises(GatewayError) as ei:
            c2.submit("r1", dict(doc, window_reads=2048))
        assert ei.value.status == 409
        # the discovery document tracks the NEW address
        assert resolve_url(root) == gw2.url
        assert GatewayClient(resolve_url(root)).status("r1")["state"] \
            == "done"
    finally:
        gw2.close()
        svc2.close()


# ---------------------------------------------------------------------------
# Typed back-pressure: 429/503 + Retry-After, honored by the client
# ---------------------------------------------------------------------------
def test_busy_429_503_and_client_policy(gateway, stub_transform):
    c = gateway["client"]
    tmp = gateway["tmp"]
    assert c.submit("b1", _doc(tmp, "b1"))["state"] == "pending"
    # slot taken (max_jobs=1): capacity -> 429 with Retry-After
    with pytest.raises(GatewayBusy) as ei:
        c.submit("b2", _doc(tmp, "b2"))
    assert ei.value.status == 429 and ei.value.kind == "capacity"
    assert ei.value.retry_after >= protocol.RETRY_AFTER_MIN_S
    # the retrying client sleeps >= the server hint and wins once the
    # slot frees (sleep recorded, not actually slept)
    sleeps = []

    def fake_sleep(s):
        sleeps.append(s)
        stub_transform["release"].set()  # free the slot mid-backoff
        gateway["svc"].wait(timeout=30)

    from adam_tpu.utils.retry import RetryPolicy

    got = c.submit_with_retry(
        "b2", _doc(tmp, "b2"),
        policy=RetryPolicy(attempts=3, backoff_s=0.01),
        sleep=fake_sleep,
    )
    assert got["state"] == "pending"
    assert sleeps and sleeps[0] >= ei.value.retry_after
    assert gateway["svc"].wait(timeout=30)
    # draining -> 503 (and the gateway's own stop_accepting answers
    # 503 even before the scheduler hears about the drain)
    gateway["gw"].stop_accepting()
    with pytest.raises(GatewayBusy) as ei:
        c.submit("b3", _doc(tmp, "b3"))
    assert ei.value.status == 503 and ei.value.kind == "draining"
    gateway["svc"].request_drain()
    with pytest.raises(GatewayBusy) as ei:
        c.submit("b4", _doc(tmp, "b4"))
    assert ei.value.status == 503
    from adam_tpu.utils import telemetry as tele

    assert tele.TRACE.snapshot()["counters"].get(tele.C_GW_BUSY, 0) >= 3


def test_gateway_accept_transient_maps_to_503(gateway, stub_transform):
    c = gateway["client"]
    faults.install("gateway.accept=transient,times=1")
    try:
        with pytest.raises(GatewayBusy) as ei:
            c.status()
        assert ei.value.status == 503
        assert ei.value.retry_after >= 1
        # one-shot clause: the next request sails through — exactly
        # what submit_with_retry's transport/busy handling rides
        assert "jobs" in c.status()
    finally:
        faults.clear()


# ---------------------------------------------------------------------------
# Wire fuzz: malformed manifests, bad routes, truncated bodies
# ---------------------------------------------------------------------------
def _raw(gateway):
    host, port = gateway["client"].host, gateway["client"].port
    return http.client.HTTPConnection(host, port, timeout=10)


def test_fuzz_bad_manifests_and_routes(gateway):
    c = gateway["client"]
    tmp = gateway["tmp"]
    # not JSON
    conn = _raw(gateway)
    conn.request("PUT", "/v1/jobs/f1", body=b"{not json",
                 headers={"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 400 and b"bad_manifest" in r.read()
    # unknown manifest field
    with pytest.raises(GatewayError) as ei:
        c.submit("f1", dict(_doc(tmp, "f1"), nope=1))
    assert ei.value.status == 400 and "nope" in str(ei.value)
    # manifest that parses but violates JobSpec validation
    with pytest.raises(GatewayError) as ei:
        c.submit("f1", dict(_doc(tmp, "f1"), weight=0))
    assert ei.value.status == 400
    # bad job id in the path
    conn.request("PUT", "/v1/jobs/..", body=b"{}",
                 headers={"Content-Length": "2"})
    assert conn.getresponse().status in (400, 404)
    # unknown routes
    for path in ("/", "/v2/jobs", "/v1/other", "/v1/jobs/f1/nope",
                 "/v1/jobs/f1/parts/a/b"):
        conn = _raw(gateway)
        conn.request("GET", path)
        assert conn.getresponse().status == 404, path
    # wrong method
    conn = _raw(gateway)
    conn.request("DELETE", "/v1/jobs")
    assert conn.getresponse().status == 405


def _recv_response(sock) -> bytes:
    """Read one full HTTP response off a raw socket (headers + the
    Content-Length'd body — a single recv can race the body's TCP
    segment)."""
    import re

    data = b""
    while b"\r\n\r\n" not in data:
        chunk = sock.recv(4096)
        if not chunk:
            return data
        data += chunk
    head, _, body = data.partition(b"\r\n\r\n")
    m = re.search(rb"[Cc]ontent-[Ll]ength: (\d+)", head)
    want = int(m.group(1)) if m else 0
    while len(body) < want:
        chunk = sock.recv(4096)
        if not chunk:
            break
        body += chunk
    return head + b"\r\n\r\n" + body


def test_fuzz_oversized_and_truncated_bodies(gateway):
    conn = _raw(gateway)
    # oversized Content-Length refused before the body is read
    conn.request("PUT", "/v1/jobs/big", headers={
        "Content-Length": str(protocol.MAX_MANIFEST_BYTES + 1),
    })
    r = conn.getresponse()
    assert r.status == 413
    doc = json.loads(r.read())
    assert doc["schema"] == protocol.ERROR_SCHEMA
    assert doc["kind"] == "too_large"
    # truncated chunked body: size line promises more than arrives
    sock = socket.create_connection(
        (gateway["client"].host, gateway["client"].port), timeout=10,
    )
    try:
        sock.sendall(
            b"PUT /v1/jobs/t1 HTTP/1.1\r\n"
            b"Host: x\r\nTransfer-Encoding: chunked\r\n\r\n"
            b"A\r\n{\"in"  # promises 10 bytes, sends 4, hangs up
        )
        sock.shutdown(socket.SHUT_WR)
        resp = _recv_response(sock)
        assert b"400" in resp.split(b"\r\n", 1)[0], resp
        assert b"truncated" in resp
    finally:
        sock.close()
    # chunked body with a garbage size line
    sock = socket.create_connection(
        (gateway["client"].host, gateway["client"].port), timeout=10,
    )
    try:
        sock.sendall(
            b"PUT /v1/jobs/t2 HTTP/1.1\r\n"
            b"Host: x\r\nTransfer-Encoding: chunked\r\n\r\n"
            b"ZZZ\r\nhello\r\n0\r\n\r\n"
        )
        resp = _recv_response(sock)
        assert b"400" in resp.split(b"\r\n", 1)[0], resp
    finally:
        sock.close()
    # a well-formed chunked manifest still parses (the happy twin)
    body = json.dumps({"input": "i", "output": "o"}).encode()
    sock = socket.create_connection(
        (gateway["client"].host, gateway["client"].port), timeout=10,
    )
    try:
        sock.sendall(
            b"PUT /v1/jobs/nope-dir HTTP/1.1\r\nHost: x\r\n"
            b"Transfer-Encoding: chunked\r\n\r\n"
            + f"{len(body):X}\r\n".encode() + body + b"\r\n0\r\n\r\n"
        )
        resp = sock.recv(4096)
        # admitted (201): chunked transfer is a first-class citizen
        assert b"201" in resp.split(b"\r\n", 1)[0], resp
    finally:
        sock.close()
        # the stub isn't armed here; the job fails and quarantines in
        # the background, which is fine — this test only cares that
        # the chunked body PARSED
        gateway["svc"].wait(timeout=60)


# ---------------------------------------------------------------------------
# Event streaming: line cursor, torn tails, resumability
# ---------------------------------------------------------------------------
def _hb_line(seq, done=False, ok=True):
    return json.dumps({"schema": HB, "seq": seq, "done": done,
                       "ok": ok}) + "\n"


def test_events_cursor_poll_and_resume(gateway, stub_transform):
    c = gateway["client"]
    tmp = gateway["tmp"]
    with pytest.raises(GatewayError) as ei:
        c.poll_events("ghost")
    assert ei.value.status == 404
    c.submit("e1", _doc(tmp, "e1"))
    hb = gateway["svc"].scheduler.heartbeat_path("e1")
    with open(hb, "w") as fh:
        fh.write(_hb_line(0) + _hb_line(1))
        fh.write('{"schema": "%s", "seq": 2, "done": false' % HB)  # torn
    cur, lines = c.poll_events("e1")
    assert [l["seq"] for l in lines] == [0, 1]  # torn tail never ships
    assert cur == 2
    # complete the torn line + append: resume from the cursor sees
    # exactly the new lines
    with open(hb, "a") as fh:
        fh.write(', "ok": true}\n' + _hb_line(3))
    cur2, lines2 = c.poll_events("e1", cursor=cur)
    assert [l["seq"] for l in lines2] == [2, 3]
    # cursor past a rotation (file now shorter): re-delivered from the
    # top instead of starving
    with open(hb, "w") as fh:
        fh.write(_hb_line(0))
    cur3, lines3 = c.poll_events("e1", cursor=cur2 + 2)
    assert [l["seq"] for l in lines3] == [0]
    # follow mode ends on done=true and survives reconnect-from-cursor
    with open(hb, "w") as fh:
        fh.write(_hb_line(0) + _hb_line(1))
    got = []

    def follow():
        for cur, line in c.events("e1", cursor=1):
            got.append((cur, line["seq"]))

    t = threading.Thread(target=follow, daemon=True)
    t.start()
    time.sleep(0.6)
    with open(hb, "a") as fh:
        fh.write(_hb_line(2, done=True))
    t.join(15)
    assert not t.is_alive()
    assert [seq for _, seq in got] == [1, 2]
    stub_transform["release"].set()
    gateway["svc"].wait(timeout=30)


# ---------------------------------------------------------------------------
# Part fetch: Range resume, sha verification, path containment
# ---------------------------------------------------------------------------
@pytest.fixture()
def fetch_job(gateway, stub_transform, tmp_path):
    """A done job whose output dir holds two synthetic parts."""
    out_dir = tmp_path / "fj.adam"
    out_dir.mkdir()
    parts = {
        "part-r-00000.parquet": os.urandom(200_000),
        "part-r-00001.parquet": os.urandom(64 * 1024),  # == chunk size
    }
    for name, data in parts.items():
        (out_dir / name).write_bytes(data)
    (out_dir / "_not-a-part").write_bytes(b"x")
    c = gateway["client"]
    c.submit("fj", {"input": "in.sam", "output": str(out_dir)})
    stub_transform["release"].set()
    assert gateway["svc"].wait(timeout=30)
    return {"parts": parts, "out_dir": str(out_dir)}


def test_part_listing_and_sha(gateway, fetch_job):
    listing = gateway["client"].list_parts("fj")
    assert listing["state"] == "done"
    got = {p["name"]: p for p in listing["parts"]}
    assert set(got) == set(fetch_job["parts"])  # _not-a-part hidden
    for name, data in fetch_job["parts"].items():
        assert got[name]["bytes"] == len(data)
        assert got[name]["sha256"] == _sha(data)


def test_fetch_resume_and_integrity(gateway, fetch_job, tmp_path):
    c = gateway["client"]
    dest = str(tmp_path / "fetched")
    name = "part-r-00000.parquet"
    data = fetch_job["parts"][name]
    # seed a partial: the first 50k a SIGKILLed client left behind
    os.makedirs(dest)
    with open(os.path.join(dest, name + ".fetch-tmp"), "wb") as fh:
        fh.write(data[:50_000])
    path = c.fetch_part("fj", name, dest)
    assert open(path, "rb").read() == data
    assert not os.path.exists(path + ".fetch-tmp")
    # a corrupt partial (right length prefix, wrong bytes) must NOT
    # publish: the sha check catches it and the retry restarts clean
    os.unlink(path)
    with open(os.path.join(dest, name + ".fetch-tmp"), "wb") as fh:
        fh.write(b"\x00" * 50_000)
    path = c.fetch_part("fj", name, dest)
    assert open(path, "rb").read() == data
    # an already-verified final file short-circuits
    before = os.path.getmtime(path)
    assert c.fetch_part("fj", name, dest) == path
    assert os.path.getmtime(path) == before
    # fetch() gets everything byte-exactly
    dest2 = str(tmp_path / "fetched2")
    fetched = c.fetch("fj", dest2)
    assert set(fetched) == set(fetch_job["parts"])
    for n, p in fetched.items():
        assert open(p, "rb").read() == fetch_job["parts"][n]


def test_fetch_range_protocol_and_containment(gateway, fetch_job):
    name = "part-r-00000.parquet"
    data = fetch_job["parts"][name]
    conn = _raw(gateway)
    conn.request("GET", f"/v1/jobs/fj/parts/{name}",
                 headers={"Range": f"bytes={len(data) - 5}-"})
    r = conn.getresponse()
    assert r.status == 206
    assert r.getheader("Content-Range") == \
        f"bytes {len(data) - 5}-{len(data) - 1}/{len(data)}"
    assert r.getheader(protocol.HDR_PART_SHA256) == _sha(data)
    assert r.read() == data[-5:]
    # start past the end: 416 with the real size for the restart
    conn.request("GET", f"/v1/jobs/fj/parts/{name}",
                 headers={"Range": f"bytes={len(data)}-"})
    r = conn.getresponse()
    assert r.status == 416
    assert r.getheader("Content-Range") == f"bytes */{len(data)}"
    r.read()
    # traversal and non-part names are unservable
    for bad in ("_not-a-part", "..%2F..%2Fetc", "part-..",
                "JOB.json"):
        conn = _raw(gateway)
        conn.request("GET", f"/v1/jobs/fj/parts/{bad}")
        assert conn.getresponse().status == 404, bad
    # fetch bytes are accounted
    from adam_tpu.utils import telemetry as tele

    assert tele.TRACE.snapshot()["counters"].get(
        tele.C_GW_BYTES_OUT, 0
    ) > 0


def test_fetch_resumes_through_midbody_fault(gateway, fetch_job,
                                             tmp_path):
    """A fault that fires AFTER the response headers aborts the
    connection (never a second status line into the framed body); the
    client keeps its partial and resumes via Range — byte-exact."""
    c = gateway["client"]
    name = "part-r-00000.parquet"
    data = fetch_job["parts"][name]
    dest = str(tmp_path / "midbody")
    # chunk 1 ships, the fault kills the connection before chunk 2;
    # the resumed attempt must complete from the 64 KiB partial
    faults.install("gateway.fetch=transient,after=1,times=1")
    try:
        path = c.fetch_part("fj", name, dest)
    finally:
        faults.clear()
    assert open(path, "rb").read() == data


def test_fetch_complete_partial_publishes_without_retransfer(
    gateway, fetch_job, tmp_path,
):
    """A client killed between the last byte and the publish leaves a
    COMPLETE .fetch-tmp: the 416 on its Range probe must verify and
    publish it, not discard it and re-download the whole part."""
    from adam_tpu.utils import telemetry as tele

    c = gateway["client"]
    name = "part-r-00000.parquet"
    data = fetch_job["parts"][name]
    dest = str(tmp_path / "complete")
    os.makedirs(dest)
    with open(os.path.join(dest, name + ".fetch-tmp"), "wb") as fh:
        fh.write(data)
    before = tele.TRACE.snapshot()["counters"].get(
        tele.C_GW_BYTES_OUT, 0
    )
    path = c.fetch_part("fj", name, dest)
    assert open(path, "rb").read() == data
    sent = tele.TRACE.snapshot()["counters"].get(
        tele.C_GW_BYTES_OUT, 0
    ) - before
    assert sent <= 1024, f"re-transferred {sent} bytes of a complete part"


def test_reput_resumes_interrupted_job(gateway, monkeypatch):
    """The cancel verb promises 'a re-submission resumes it': an
    identical re-PUT of an interrupted job must re-admit (201) and
    resume, not short-circuit as an idempotent duplicate."""
    from adam_tpu.pipelines.streamed import RunCancelled

    calls = []

    def fake(inp, out, **kw):
        calls.append(bool(kw.get("resume")))
        if len(calls) == 1:
            raise RunCancelled("cancelled at a window boundary")
        return {"n_reads": 0, "windows_fresh": 0}

    monkeypatch.setattr(sched_mod.streamed_mod, "transform_streamed",
                        fake)
    c = gateway["client"]
    doc = _doc(gateway["tmp"], "ij")
    assert c.submit("ij", doc)["state"] == "pending"
    assert gateway["svc"].wait(timeout=30)
    assert c.status("ij")["state"] == "interrupted"
    again = c.submit("ij", doc)  # NOT a duplicate: a resume
    # the resume KEEPS the original trace — one job, one trace across
    # attempts
    assert again.pop("trace_id")
    assert again == {"job_id": "ij", "state": "pending"}
    assert gateway["svc"].wait(timeout=30)
    assert c.status("ij")["state"] == "done"
    assert calls == [False, True]  # the second run resumed


# ---------------------------------------------------------------------------
# Cancel
# ---------------------------------------------------------------------------
def test_cancel_states(gateway, stub_transform):
    c = gateway["client"]
    tmp = gateway["tmp"]
    with pytest.raises(GatewayError) as ei:
        c.cancel("ghost")
    assert ei.value.status == 404
    c.submit("c1", _doc(tmp, "c1"))
    got = c.cancel("c1")
    assert got == {"job_id": "c1", "cancelling": True}
    stub_transform["release"].set()
    assert gateway["svc"].wait(timeout=30)
    # terminal job: nothing to cancel
    with pytest.raises(GatewayError) as ei:
        c.cancel("c1")
    assert ei.value.status == 409


# ---------------------------------------------------------------------------
# End-to-end over real sockets: two clients, two tenants, real pipeline
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def gw_input(tmp_path_factory):
    """Synthetic input + solo fault-free baseline (numpy backend)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from make_synth_sam import make_sam

    work = tmp_path_factory.mktemp("gateway")
    path = str(work / "in.sam")
    make_sam(path, 4096, 100)
    solo = str(work / "solo.adam")
    os.environ["ADAM_TPU_BQSR_BACKEND"] = "numpy"
    try:
        from adam_tpu.pipelines.streamed import transform_streamed

        transform_streamed(path, solo, window_reads=512)
    finally:
        os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
    return {"input": path, "baseline": _parts_hash(solo)}


def test_two_clients_two_tenants_end_to_end(tmp_path, gw_input,
                                            monkeypatch):
    """The ISSUE-11 acceptance scenario: two jobs submitted
    concurrently by two independent HTTP clients against a live
    gateway, streamed to completion, results downloaded over the wire
    — everything byte-identical to the solo runs."""
    monkeypatch.setenv("ADAM_TPU_BQSR_BACKEND", "numpy")
    svc = TransformService(str(tmp_path / "root"), max_jobs=2)
    gw = GatewayServer(svc)
    gw.start()
    outs = {jid: str(tmp_path / f"{jid}.adam") for jid in ("ga", "gb")}
    results = {}
    errors = []

    def one_client(jid, tenant, weight):
        try:
            c = GatewayClient(gw.url)  # each client its own instance
            got = c.submit_with_retry(jid, {
                "input": gw_input["input"], "output": outs[jid],
                "tenant": tenant, "weight": weight,
                "window_reads": 512,
            }, deadline_s=120)
            assert got["state"] == "pending", got
            # follow the event stream to completion (live status via
            # the resumable NDJSON stream, not local file access)
            final = None
            for _cur, line in c.events(jid):
                final = line
            assert final and final.get("done"), final
            assert final.get("ok") is True, final
            # download the results over the wire
            dest = str(tmp_path / f"fetched-{jid}")
            fetched = c.fetch(jid, dest)
            results[jid] = {
                "final": final,
                "fetched": {
                    n: _sha(open(p, "rb").read())
                    for n, p in fetched.items()
                },
            }
        except Exception as e:  # surfaced by the main thread
            errors.append((jid, e))

    threads = [
        threading.Thread(target=one_client, args=("ga", "A", 2.0)),
        threading.Thread(target=one_client, args=("gb", "B", 1.0)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
        assert not t.is_alive(), "client thread hung"
    assert not errors, errors
    assert svc.wait(timeout=60)
    for jid in outs:
        # the job's server-side output is byte-identical to solo...
        assert _parts_hash(outs[jid]) == gw_input["baseline"], jid
        # ...and so is every part the client downloaded over HTTP
        assert results[jid]["fetched"] == gw_input["baseline"], jid
    # remote top renders the finished board and exits clean
    from adam_tpu.utils import top as top_mod

    assert top_mod.follow_url(gw.url, once=True,
                              out=open(os.devnull, "w")) == 0
    assert top_mod.follow_url(gw.url, interval=0.1, max_wait_s=30,
                              out=open(os.devnull, "w")) == 0
    gw.close()
    svc.close()


def test_top_url_unreachable_exits_2():
    from adam_tpu.utils import top as top_mod

    # a port nothing listens on: exit 2, the no-stream contract
    assert top_mod.follow_url("http://127.0.0.1:9", once=True,
                              out=open(os.devnull, "w")) == 2


# ---------------------------------------------------------------------------
# SIGTERM drain ordering through the real CLI (subprocess)
# ---------------------------------------------------------------------------
_DRIVER = """\
import sys
from adam_tpu.cli.main import main
sys.exit(main(sys.argv[1:]))
"""


def _gw_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["ADAM_TPU_BQSR_BACKEND"] = "numpy"
    env.setdefault("ADAM_TPU_NO_COMPILE_CACHE", "1")
    env["ADAM_TPU_PROGRESS_INTERVAL_S"] = "0.2"
    env.pop("ADAM_TPU_FAULTS", None)
    return env


def test_serve_listen_sigterm_drain_exit0(tmp_path, gw_input):
    """SIGTERM a live gateway: stop accepting -> 503 -> scheduler
    drain -> settled -> exit 0 (docs/SERVING.md drain ordering), with
    every JOB.json durably terminal and the run resumable."""
    root = str(tmp_path / "root")
    out = str(tmp_path / "sj.adam")
    proc = subprocess.Popen(
        [sys.executable, "-c", _DRIVER, "serve", root,
         "--listen", "127.0.0.1:0", "--max-jobs", "2"],
        env=_gw_env(), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    try:
        # discovery: gateway.json appears once the socket is bound
        deadline = time.monotonic() + 60
        gw_json = os.path.join(root, "gateway.json")
        while time.monotonic() < deadline:
            if os.path.isfile(gw_json):
                break
            assert proc.poll() is None, \
                proc.communicate()[0].decode(errors="replace")
            time.sleep(0.05)
        c = GatewayClient(resolve_url(root))
        got = c.submit_with_retry("sj", {
            "input": gw_input["input"], "output": out,
            "window_reads": 512,
        }, deadline_s=60)
        assert got["state"] == "pending"
        # wait for the job to be genuinely mid-flight, then SIGTERM
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if os.path.isfile(os.path.join(root, "sj",
                                           "heartbeat.ndjson")):
                break
            time.sleep(0.05)
        proc.send_signal(signal.SIGTERM)
        stdout, _ = proc.communicate(timeout=120)
        assert proc.returncode == 0, stdout.decode(errors="replace")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    # settled: the job's JOB.json is durably terminal
    doc = json.load(open(os.path.join(root, "sj", "JOB.json")))
    assert doc["state"] in ("done", "interrupted")
    # a rerun (recover + resume, no gateway needed) completes the job
    # byte-identically if the drain interrupted it
    rc = subprocess.run(
        [sys.executable, "-c", _DRIVER, "serve", root],
        env=_gw_env(), cwd=REPO, capture_output=True,
    )
    assert rc.returncode == 0, rc.stdout + rc.stderr
    assert _parts_hash(out) == gw_input["baseline"]
    doc = json.load(open(os.path.join(root, "sj", "JOB.json")))
    assert doc["state"] == "done"


# ---------------------------------------------------------------------------
# Observability surfaces: /metrics, /v1/jobs/<id>/trace, /incidents
# (docs/OBSERVABILITY.md "Gateway observability surfaces")
# ---------------------------------------------------------------------------
def _scrape_value(text, name):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split(" ", 1)[1])
    return None


def test_metrics_scrapes_exposition_and_monotonic(gateway,
                                                  stub_transform):
    from adam_tpu.utils import telemetry as tele

    c = gateway["client"]
    first = c.metrics()
    second = c.metrics()
    for text in (first, second):
        assert text.endswith("\n")
        # every non-comment sample line is name[{labels}] value, the
        # name valid per the exposition grammar
        for line in text.splitlines():
            if line.startswith("#") or not line:
                continue
            name = line.split("{")[0].split(" ")[0]
            assert tele.prometheus_name_valid(name), line
        assert "# TYPE adam_tpu_gateway_metrics_scrapes counter" in text
        assert "adam_tpu_traces_active" in text
    # each scrape counts ITSELF before snapshotting, so consecutive
    # scrapes read strictly increasing adam_tpu_gateway_metrics_scrapes
    s1 = _scrape_value(first, "adam_tpu_gateway_metrics_scrapes")
    s2 = _scrape_value(second, "adam_tpu_gateway_metrics_scrapes")
    assert s1 is not None and s2 is not None and s2 > s1
    # gateway.requests surfaces too — it counts in the handler's
    # finally AFTER the response is written, so allow the bump from an
    # earlier scrape a moment to land
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        v = _scrape_value(c.metrics(), "adam_tpu_gateway_requests")
        if v is not None and v >= 1:
            break
        time.sleep(0.05)
    else:
        pytest.fail("gateway.requests never surfaced in /metrics")


def test_job_trace_endpoint_and_trace_id_echo(gateway, stub_transform):
    import re

    from adam_tpu.utils import telemetry as tele

    c = gateway["client"]
    tmp = gateway["tmp"]
    got = c.submit("tj", _doc(tmp, "tj"))
    tid = got["trace_id"]
    assert re.fullmatch(r"[0-9a-f]{16}", tid)
    # a duplicate-safe re-PUT echoes the SAME trace: one job, one trace
    again = c.submit("tj", _doc(tmp, "tj"))
    assert again["duplicate"] is True and again["trace_id"] == tid
    stub_transform["release"].set()
    assert gateway["svc"].wait(timeout=30)
    doc = c.job_trace("tj")
    assert doc["job_id"] == "tj" and doc["trace_id"] == tid
    names = {e["name"] for e in doc["traceEvents"]
             if e.get("ph") == "X"}
    # the trace spans the job's lifecycle: the gateway submit root and
    # the scheduler's run umbrella (the stub replaces the streamed leg)
    assert tele.SPAN_GW_SUBMIT in names
    assert tele.SPAN_SCHED_JOB in names
    # every X event in the filtered view belongs to this trace —
    # stamped or linked, never a stranger
    for e in doc["traceEvents"]:
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        assert args.get("trace") == tid or any(
            l.get("trace") == tid for l in args.get("links") or []
        ), e
    # unknown job: typed 404
    with pytest.raises(GatewayError) as ei:
        c.job_trace("nope")
    assert ei.value.status == 404


def test_incidents_endpoint_lists_run_root_bundles(gateway,
                                                   stub_transform,
                                                   monkeypatch):
    from adam_tpu.utils import incidents as incidents_mod

    # an earlier test in this worker may have fired the same trigger
    # inside its cooldown
    monkeypatch.setenv("ADAM_TPU_INCIDENT_COOLDOWN_S", "0")
    c = gateway["client"]
    empty = c.incidents()
    assert empty["schema"] == protocol.INCIDENTS_SCHEMA
    assert empty["incidents"] == []
    # the serve ctor armed the recorder on its run root: a trigger
    # fired anywhere in-process surfaces on the wire
    assert incidents_mod.incidents_dir() == os.path.join(
        gateway["root"], incidents_mod.INCIDENTS_DIRNAME
    )
    path = incidents_mod.maybe_record(
        "hedge.fired", reason="wire-visibility probe"
    )
    assert path is not None
    rows = c.incidents()["incidents"]
    assert [r["trigger"] for r in rows] == ["hedge.fired"]
    assert rows[0]["reason"] == "wire-visibility probe"
