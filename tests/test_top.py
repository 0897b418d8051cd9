"""``adam-tpu top`` dashboard (utils/top.py): heartbeat parsing (torn
lines, both schema versions), frame rendering, and the follow loop's
exit contract (0 on done+ok, 1 on done+!ok, 2 on no stream)."""

import io
import json
import os
import time

from adam_tpu.cli.main import main
from adam_tpu.utils import telemetry as tele
from adam_tpu.utils import top as top_mod


def _line(**over):
    base = {
        "schema": tele.HEARTBEAT_SCHEMA,
        "seq": 0,
        "elapsed_s": 1.5,
        "windows_ingested": 2,
        "windows_total": 4,
        "windows_resumed": 1,
        "parts_written": 1,
        "reads_ingested": 5000,
        "reads_per_s": 3333.3,
        "bytes_written": 1 << 20,
        "h2d_bytes": 10 << 20,
        "d2h_bytes": 5 << 20,
        "hbm_bytes_in_use": {"0": 1 << 30},
        "hbm_peak_bytes": 2 << 30,
        "inflight": 2,
        "inflight_per_device": {"0": 1, "1": 1},
        "retries": 3,
        "faults": 1,
        "devices_evicted": 0,
        "eta_s": 4.5,
        "done": False,
        "ok": True,
    }
    base.update(over)
    return base


def test_parse_ignores_torn_tail_and_junk():
    good = json.dumps(_line(seq=0)) + "\n" + json.dumps(_line(seq=1)) + "\n"
    text = good + "not json\n" + json.dumps(_line(seq=2))  # no newline
    lines = top_mod.parse_heartbeat_text(text)
    # the junk line stops nothing, the unterminated tail is deferred
    assert [l["seq"] for l in lines] == [0, 1]
    # a /1 line (no ledger fields) still parses
    v1 = {k: v for k, v in _line().items()
          if k not in ("h2d_bytes", "d2h_bytes", "hbm_bytes_in_use",
                       "hbm_peak_bytes")}
    v1["schema"] = "adam_tpu.heartbeat/1"
    assert top_mod.parse_heartbeat_text(json.dumps(v1) + "\n")


def test_render_frame_contents():
    text = top_mod.render_frame(_line(), source="hb.ndjson")
    assert "RUNNING" in text
    assert "2/4" in text and "resumed 1" in text and "parts 1" in text
    assert "5,000" in text
    assert "10.0MiB" in text and "5.0MiB" in text  # transfer totals
    assert "1.0GiB" in text and "peak 2.0GiB" in text
    assert "retries 3" in text and "faults 1" in text
    done = top_mod.render_frame(_line(done=True, ok=True))
    assert "DONE" in done and "run complete" in done
    failed = top_mod.render_frame(_line(done=True, ok=False))
    assert "FAILED" in failed and "ok=false" in failed
    # /1 line without HBM fields: no fabricated zeros, no crash
    v1 = {k: v for k, v in _line().items()
          if not k.startswith(("h2d", "d2h", "hbm"))}
    text = top_mod.render_frame(v1)
    assert "h2d -" in text and "hbm" not in text.splitlines()[5]


def test_follow_exit_codes(tmp_path, capsys):
    p = str(tmp_path / "hb.ndjson")
    with open(p, "w") as fh:
        fh.write(json.dumps(_line(seq=0)) + "\n")
        fh.write(json.dumps(_line(seq=1, done=True, ok=True)) + "\n")
    out = io.StringIO()
    assert top_mod.follow(p, interval=0.01, out=out) == 0
    assert "DONE" in out.getvalue()
    # crashed run: final line ok=false -> exit 1
    with open(p, "w") as fh:
        fh.write(json.dumps(_line(done=True, ok=False)) + "\n")
    assert top_mod.follow(p, interval=0.01, out=io.StringIO()) == 1
    # missing file in -once mode -> exit 2
    assert top_mod.follow(str(tmp_path / "nope.ndjson"), once=True,
                          out=io.StringIO()) == 2
    # empty file in -once mode -> exit 2
    empty = str(tmp_path / "empty.ndjson")
    open(empty, "w").close()
    assert top_mod.follow(empty, once=True, out=io.StringIO()) == 2
    # live (not done) stream in -once mode renders one frame, exit 0
    live = str(tmp_path / "live.ndjson")
    with open(live, "w") as fh:
        fh.write(json.dumps(_line(seq=0)) + "\n")
    out = io.StringIO()
    assert top_mod.follow(live, once=True, out=out) == 0
    assert "RUNNING" in out.getvalue()


def test_follow_survives_rotation_truncate(tmp_path):
    """A file that shrinks (the heartbeat rotated it) re-reads from the
    top instead of wedging on a stale offset."""
    p = str(tmp_path / "hb.ndjson")
    big = json.dumps(_line(seq=0, reads_ingested=10**9)) + "\n"
    with open(p, "w") as fh:
        fh.write(big * 5)
    out = io.StringIO()
    assert top_mod.follow(p, once=True, out=out) == 0
    # simulate rotation: much smaller fresh file carrying the final line
    with open(p, "w") as fh:
        fh.write(json.dumps(_line(seq=9, done=True)) + "\n")
    assert top_mod.follow(p, interval=0.01, out=io.StringIO()) == 0


def test_top_cli_subcommand(tmp_path, capsys):
    p = str(tmp_path / "hb.ndjson")
    with open(p, "w") as fh:
        fh.write(json.dumps(_line(done=True)) + "\n")
    assert main(["top", p, "-once"]) == 0
    assert "adam-tpu top" in capsys.readouterr().out
    assert main(["top", str(tmp_path / "missing"), "-once"]) == 2


# ---------------------------------------------------------------------------
# Multi-job view (serve run-root aggregation)
# ---------------------------------------------------------------------------
def _job_stream(root, job, *lines):
    d = root / job
    d.mkdir(parents=True, exist_ok=True)
    with open(d / "heartbeat.ndjson", "w") as fh:
        for ln in lines:
            fh.write(json.dumps(ln) + "\n")


def test_render_multi_frame_sums_and_states(tmp_path):
    """Per-job rows + summed job-scoped totals; JOB.json states win
    over the heartbeat's done/ok heuristic."""
    jobs = {
        "a": _line(done=True, ok=True, parts_written=3,
                   bytes_written=100, reads_ingested=10),
        "b": _line(done=False, parts_written=2, bytes_written=50,
                   reads_ingested=5, reads_per_s=7.0),
        "c": _line(done=True, ok=False, parts_written=0),
    }
    frame = top_mod.render_multi_frame(
        jobs, root="R", states={"c": "interrupted"},
        pool={"h2d_bytes": 9 << 20, "d2h_bytes": 1 << 20,
              "retries": 4, "faults": 2},
    )
    assert "multi-job R" in frame and "3 job(s)" in frame
    assert "DONE" in frame and "RUNNING" in frame
    assert "INTERRUPTED" in frame and "FAILED" not in frame
    assert "parts 5" in frame  # 3 + 2 + 0 summed
    assert "1 running  1 done  1 stopped/failed" in frame
    assert "retries 4" in frame and "faults 2" in frame


def test_follow_root_exit_codes_and_midwatch_join(tmp_path):
    root = tmp_path / "run-root"
    root.mkdir()
    # no streams yet: bounded wait exits 2
    assert top_mod.follow_root(
        str(root), interval=0.01, once=True, out=io.StringIO()
    ) == 2
    # two jobs, all done+ok -> 0 (the service's own stream at the root
    # is pool totals, not a job; done=true = the scheduler closed)
    _job_stream(root, "jobA", _line(seq=0), _line(seq=1, done=True))
    _job_stream(root, "jobB", _line(seq=0, done=True))
    with open(root / "heartbeat.ndjson", "w") as fh:
        fh.write(json.dumps(_line(seq=0, done=True)) + "\n")
    out = io.StringIO()
    assert top_mod.follow_root(str(root), interval=0.01, out=out) == 0
    txt = out.getvalue()
    assert "jobA" in txt and "jobB" in txt
    assert "2 job(s)" in txt
    # one job ends ok=false -> 1 (a genuine failure)
    _job_stream(root, "jobB", _line(seq=0, done=True, ok=False))
    assert top_mod.follow_root(
        str(root), interval=0.01, out=io.StringIO()
    ) == 1
    # ...but ok=false from a graceful drain (durable JOB.json says
    # interrupted) is a clean stop, not a failure -> 0
    (root / "jobB").mkdir(exist_ok=True)
    with open(root / "jobB" / "JOB.json", "w") as fh:
        json.dump({"state": "interrupted"}, fh)
    assert top_mod.follow_root(
        str(root), interval=0.01, out=io.StringIO()
    ) == 0
    os.unlink(root / "jobB" / "JOB.json")
    # a job appearing mid-watch joins the board before exit; a LIVE
    # service stream keeps the watch open even with every discovered
    # job done (capacity-queued jobs may have no stream yet)
    import threading

    with open(root / "heartbeat.ndjson", "w") as fh:
        fh.write(json.dumps(_line(seq=0)) + "\n")  # service live again

    def late_join():
        time.sleep(0.15)
        _job_stream(root, "jobC", _line(seq=0, done=True))
        _job_stream(root, "jobB", _line(seq=1, done=True))
        with open(root / "heartbeat.ndjson", "a") as fh:
            fh.write(json.dumps(_line(seq=1, done=True)) + "\n")

    _job_stream(root, "jobB", _line(seq=0))  # live again
    t = threading.Thread(target=late_join)
    t.start()
    out = io.StringIO()
    assert top_mod.follow_root(
        str(root), interval=0.02, out=out, max_wait_s=30
    ) == 0
    t.join()
    assert "jobC" in out.getvalue()


def test_top_cli_multi_job_directory(tmp_path, capsys):
    root = tmp_path / "serve-root"
    _job_stream(root, "j1", _line(done=True))
    assert main(["top", str(root), "-once"]) == 0
    assert "multi-job" in capsys.readouterr().out


def test_render_frame_slo_cell():
    # /7 producer with an armed SLO engine: worst burn + regressions
    text = top_mod.render_frame(
        _line(slo_worst_burn=14.4, perf_regressions=2),
        source="hb.ndjson")
    assert "burn 14.4x" in text
    assert "perf regressions 2" in text
    # regressions without an SLO engine still renders the cell
    text = top_mod.render_frame(
        _line(slo_worst_burn=None, perf_regressions=1),
        source="hb.ndjson")
    assert "no slo" in text and "perf regressions 1" in text
    # a pre-/7 producer (no fields at all) renders no slo cell
    text = top_mod.render_frame(_line(), source="hb.ndjson")
    assert "slo  " not in text


def test_cli_once_long_flag_and_exit_codes(tmp_path, capsys):
    p = str(tmp_path / "hb.ndjson")
    with open(p, "w") as fh:
        fh.write(json.dumps(_line(slo_worst_burn=2.0)) + "\n")
    assert main(["top", p, "--once"]) == 0  # long spelling
    assert "burn 2.0x" in capsys.readouterr().out
    with open(p, "a") as fh:
        fh.write(json.dumps(_line(seq=1, done=True, ok=False)) + "\n")
    assert main(["top", p, "--once"]) == 1
    capsys.readouterr()
    assert main(["top", str(tmp_path / "absent.ndjson"), "--once"]) == 2
