"""The harness is driven by data: a configuration, a traffic mix and a
metric added as files, with entries in BENCHMARK.json, run with no
existing file edited; a metric whose source matches nothing is left out
of the result line; a run without a TPU, or without the program, fails
and prints no result."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

sys.path.insert(0, os.path.dirname(__file__))

import helpers  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_added_files_run_without_edits(tmp_path):
    root = helpers.make_root(str(tmp_path))
    before = _digests(root)
    bdir = os.path.join(root, "benchmark")
    helpers.add_config(root, "tiny2", contig_len=72_000)
    shutil.copy(os.path.join(bdir, "traffic", "discover.json"),
                os.path.join(bdir, "traffic", "again.json"))
    helpers.add_cell(root, "tiny2.again", "tiny2", "again")
    new_metrics = {
        "total_s_per_mread": {"reader": "span_per_mread",
                              "span": "streamed.total"},
        "absent_s_per_mread": {"reader": "span_per_mread",
                               "span": "streamed.no_such_stage"},
        "pass_c_share": {},
    }
    with open(os.path.join(bdir, "metrics", "pass_c_share.py"), "w") as fh:
        fh.write("def read(ctx, spec):\n"
                 "    s = [j['snap']['spans'] for j in ctx['layer_jobs']]\n"
                 "    c = sum(x['streamed.pass_c']['total_s'] for x in s)\n"
                 "    t = sum(x['streamed.total']['total_s'] for x in s)\n"
                 "    return 100.0 * c / t\n")
    for name, spec in new_metrics.items():
        unit = "%" if name.endswith("share") else "s/Mread"
        with open(os.path.join(bdir, "metrics", name + ".json"), "w") as fh:
            json.dump(spec, fh)
        helpers.edit_bench(root, lambda b, n=name, u=unit: b["per_layer"].append(
            {"name": n, "unit": u, "better": "lower",
             "source": "program_span", "layer": "streamed passes",
             "moves": "reads_per_s", "workloads": ["tiny2.again"]}))
    after = _digests(root)
    assert all(after[p] == d for p, d in before.items())

    res = helpers.result(helpers.drive(root, "tiny2.again", trace=1))
    m = res["metrics"]
    assert m["total_s_per_mread"]["value"] > 0
    assert m["pass_c_share"]["unit"] == "%"  # BENCHMARK.json's unit
    assert 0 < m["pass_c_share"]["value"] < 100
    assert "absent_s_per_mread" not in m  # nothing to read: left out
    # the flagship's metrics name their cells, so a new cell owes none
    assert "apply_fetch_s_per_mread" not in m and res["correct"]
    # the metrics added for tiny2.again are not the old cell's
    res0 = helpers.result(helpers.drive(root, "tiny.discover", trace=0))
    assert set(res0["metrics"]) == {"reads_per_s", "setup_s"}
    assert res0["device"]["platform"] == "cpu"


def test_run_without_tpu_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(helpers.BENCH, "run.py"), "--workload",
         "wgs100.discover.1chip", "--seed", "3", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300, cwd=helpers.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_peaks_by_device_kind():
    import pytest

    sys.path.insert(0, helpers.BENCH)
    import run

    v5e = run.peaks("TPU v5 lite")
    assert v5e["bf16_flop_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v4", "cpu", "source"):
        with pytest.raises(run.BenchError):
            run.peaks(kind)


def test_run_without_program_fails(tmp_path):
    root = helpers.make_root(str(tmp_path))
    os.remove(os.path.join(root, "adam_tpu"))
    p = helpers.drive(root, "tiny.discover", program=False)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_benchmark_json_keeps_the_contract():
    b = json.load(open(os.path.join(helpers.REPO, "BENCHMARK.json")))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"]
    assert 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]]
    cells = [w["name"] for w in b["workloads"]]
    e2e = [m["name"] for m in b["end_to_end"]]
    assert "setup_s" in e2e
    for n in names + cells + e2e + [m["name"] for m in b["per_layer"]]:
        assert NAME.match(n), n
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/")
        assert os.path.exists(os.path.join(helpers.REPO, c["file"]))
        assert c["name"] in {w["config"] for w in b["workloads"]}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(helpers.BENCH, "traffic",
                                           w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        # the metric's file says only how to read it
        spec = json.load(open(os.path.join(helpers.BENCH, "metrics",
                                           m["name"] + ".json")))
        assert not set(spec) & {"unit", "better", "source", "layer", "moves"}
    assert len(json.dumps(b)) < 64 * 1024
