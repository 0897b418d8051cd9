"""The traffic generator: seeded bytes and the configuration's shares."""

import filecmp
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "traffic"))

import gen_wgs  # noqa: E402
import helpers  # noqa: E402
import reference  # noqa: E402


def _cfg(**over):
    cfg = json.load(open(os.path.join(helpers.BENCH, "configs",
                                      "wgs-pe100-31x.json")))
    cfg.update(helpers.TINY, **over)
    return cfg


def test_same_seed_same_bytes(tmp_path):
    cfg = _cfg(n_reads=6000)
    dirs = [tmp_path / d for d in ("a", "b", "c")]
    for d in dirs:
        d.mkdir()
    seed = 2**31 + 99  # beyond 32 signed bits, as the driver's are
    gen_wgs.generate(str(dirs[0]), seed, cfg, procs=1)
    gen_wgs.generate(str(dirs[1]), seed, cfg, procs=3)
    gen_wgs.generate(str(dirs[2]), seed + 1, cfg, procs=1)
    for f in ("input.sam", "known.vcf", "truth.npz"):
        assert filecmp.cmp(dirs[0] / f, dirs[1] / f, shallow=False), f
    assert not filecmp.cmp(dirs[0] / "input.sam", dirs[2] / "input.sam",
                           shallow=False)


def test_spawned_workers_through_the_harness(tmp_path):
    """The harness's own call, in a process whose path holds only the
    benchmark's root, as in a run: the spawned workers import the
    generator and write the same bytes as one process."""
    import subprocess

    cfg = _cfg(n_reads=6000)
    code = ("import json, sys; sys.path.insert(0, sys.argv[1]); import run\n"
            "if __name__ == '__main__':\n"
            "    run.generate(json.loads(sys.argv[2]), {'generator': 'gen_wgs'},"
            " 7, sys.argv[3], procs=int(sys.argv[4]))\n")
    for d, procs in (("one", 1), ("three", 3)):
        (tmp_path / d).mkdir()
        subprocess.run([sys.executable, "-c", code, helpers.BENCH,
                        json.dumps(cfg), str(tmp_path / d), str(procs)],
                       check=True, timeout=120, cwd=str(tmp_path))
    for f in ("input.sam", "known.vcf", "truth.npz"):
        assert filecmp.cmp(tmp_path / "one" / f, tmp_path / "three" / f,
                           shallow=False), f


def test_shares_match_configuration(tmp_path):
    cfg = _cfg()
    p = gen_wgs.generate(str(tmp_path), 5, cfg, procs=1)
    inp = reference.load_input(p["sam"])
    assert inp["n"] == cfg["n_reads"]
    flags = inp["flags"]
    mapped = (flags & reference.FLAG_UNMAPPED) == 0
    unmapped_pairs = (~mapped[0::2]).mean()
    assert abs(unmapped_pairs - cfg["unmapped_frac"]) < 0.005
    dup = reference.markdup(inp)
    dup_pairs = dup[0::2][mapped[0::2]].mean()
    assert abs(dup_pairs - cfg["dup_frac"]) < 0.02
    cig = inp["cigar"].to_pylist()
    clipped = np.array(["S" in c for c in cig])[mapped].mean()
    assert abs(clipped - cfg["clip_frac"]) < 0.01
    # the truth: every mapped read has a cost; misaligned reads cost more
    # as written than their true alignment
    truth = np.load(p["truth"])["truth_cost"]
    assert np.all((truth >= 0) == mapped)
    assert (truth[mapped] > 0).mean() > 0.2  # errors, SNPs, indels


def test_reads_stay_inside_their_contig(tmp_path):
    cfg = _cfg(n_reads=8000, contig_len=8000)
    p = gen_wgs.generate(str(tmp_path), 11, cfg, procs=1)
    inp = reference.load_input(p["sam"])
    mapped = (inp["flags"] & reference.FLAG_UNMAPPED) == 0
    cg = reference._cigar_cols(inp["cigar"])
    end = inp["start"] + cg["m1"] + cg["dl"] + cg["m2"]
    assert np.all(end[mapped] <= cfg["contig_len"])
    seq_len = np.array([len(s) for s in inp["seq"].to_pylist()])
    q_len = cg["lead"] + cg["m1"] + cg["il"] + cg["m2"] + cg["trail"]
    assert np.all(seq_len[mapped] == q_len[mapped])
