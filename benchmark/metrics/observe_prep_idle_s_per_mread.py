"""Device idle time under observe's host prep: the traced job's
host-plane annotations named by the file's ``span``
(``bqsr.observe.prep``), clipped to the ``bench.job`` annotation; for
each chip of the cell, the part of those intervals in which the chip
ran no op; averaged over the chips and divided by the traced job's
input reads in millions.  None when the trace holds no such
annotation.

The annotations are read from the run's profile (``<root>/.bench/xprof``,
the harness's work directory), the same ``.xplane.pb`` the device
metrics come from, so host and device intervals share one clock."""

from __future__ import annotations

import os
import re

import devtrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
XPROF = os.path.join(ROOT, ".bench", "xprof")
HOST_PLANE = r"^/host:"


def host_intervals(path: str, name: str, job: tuple) -> list:
    """Merged (start, end) of the host-plane events called ``name``,
    clipped to ``job``."""
    from jax.profiler import ProfileData

    a, b = job
    found = []
    host = re.compile(HOST_PLANE)
    for plane in ProfileData.from_file(path).planes:
        if not host.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != name:
                    continue
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e > a and s < b:
                    found.append((max(s, a), min(e, b)))
    return devtrace._union(found)


def idle_under(intervals: list, busy: list) -> float:
    """Nanoseconds of ``intervals`` not covered by ``busy`` (both merged
    and sorted)."""
    idle = 0
    k = 0
    for s, e in intervals:
        covered = 0
        while k < len(busy) and busy[k][1] <= s:
            k += 1
        j = k
        while j < len(busy) and busy[j][0] < e:
            covered += min(e, busy[j][1]) - max(s, busy[j][0])
            j += 1
        idle += (e - s) - covered
    return idle


def read(ctx, spec):
    tr = ctx.get("trace")
    chips = sorted(ctx.get("idle_pct") or {})
    if tr is None or tr.job is None or not chips:
        return None
    path = devtrace.find_xplane(XPROF)
    if path is None:
        return None
    prep = host_intervals(path, spec["span"], tr.job)
    if not prep:
        return None
    per_chip = []
    for chip in chips:
        busy = devtrace._union(
            [(s, e) for s, e, _ in tr._clip(tr.ops.get(chip, []))])
        per_chip.append(idle_under(prep, busy) / 1e9)
    mreads = ctx["traced_job"]["reads"] / 1e6
    return sum(per_chip) / len(per_chip) / mreads
