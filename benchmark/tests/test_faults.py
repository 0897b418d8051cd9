"""Whole runs on the CPU at a test size with the timed path broken
underneath: each fault the cells can have turns ``correct`` false, and
so do the traffic file's variants, the program with known-site masking
or realignment switched off.  The unbroken runs, on one device and on four, are correct."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))

import helpers  # noqa: E402

CASES = [
    # fault, devices, the number it has to fail (None: a correct run)
    ("none", 1, None),
    ("half_batch", 1, "records_wrong"),
    ("altered_answer", 1, "qual_wrong"),
    ("unchanged", 1, "dup_flags_wrong"),
    ("no_known_sites", 1, "qual_wrong"),
    ("no_realign", 1, "realign_left_pct"),
    ("half_realign", 1, "realign_left_pct"),
    ("none", 4, None),
    ("no_exchange", 4, "qual_wrong"),
]


@pytest.mark.parametrize("fault,devices,fails", CASES,
                         ids=[f"{c[0]}-{c[1]}dev" for c in CASES])
def test_fault_turns_correct_false(tmp_path, fault, devices, fails):
    root = helpers.make_root(str(tmp_path), devices=devices)
    res = helpers.result(helpers.drive(root, "tiny.discover", fault,
                                       devices=devices))
    checks = res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    if fails is None:
        assert res["correct"], checks
    else:
        assert not res["correct"]
        assert checks[fails]["value"] > checks[fails]["limit"], checks
