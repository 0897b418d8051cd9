"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Device time is what ran on the device: the events of each device
plane's op line.  Busy time is the union of those intervals inside the
traced job, idle share 1 - busy / wall, per chip.  Program time is the
summed duration of the module-line events whose names match a metric's
patterns.  Idle gaps are labelled with the innermost ``streamed.*`` stage
the host had open at their midpoint; the host spans are put on the
profiler's clock by the ``bench.job`` annotation the harness records at
each traced job's start.
"""

from __future__ import annotations

import glob
import os
import re

#: Planes and lines that hold device work on a TPU.
DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
JOB_ANNOTATION = "bench.job"


def find_xplane(log_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def _union(intervals: list) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Trace:
    """The events of one traced job, read once from an ``.xplane.pb``.

    ``device_plane``/``op_line``/``module_line`` select what counts as
    device work (a CPU trace in the tests selects the XLA threads)."""

    def __init__(self, path: str, device_plane: str = DEVICE_PLANE,
                 op_line: str = OP_LINE, module_line: str = MODULE_LINE,
                 annotation: str = JOB_ANNOTATION):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        self.planes = {}
        self.ops = {}      # plane -> [(start, end, name)]
        self.modules = {}  # plane -> [(start, end, name)]
        self.job = None    # (start, end) of the annotation, profiler clock
        dev = re.compile(device_plane)
        for plane in pd.planes:
            lines = {}
            for line in plane.lines:
                lines[line.name] = line
                if self.job is None:
                    for ev in line.events:
                        if ev.name == annotation:
                            self.job = (ev.start_ns,
                                        ev.start_ns + ev.duration_ns)
                            break
            self.planes[plane.name] = sorted(lines)
            if not dev.match(plane.name):
                continue
            for key, want, store in (("op", op_line, self.ops),
                                     ("mod", module_line, self.modules)):
                evs = []
                for name, line in lines.items():
                    if re.match(want, name):
                        evs += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name) for ev in line.events]
                store[plane.name] = evs

    def _clip(self, evs: list) -> list:
        if self.job is None:
            return evs
        a, b = self.job
        return [(max(s, a), min(e, b), n) for s, e, n in evs if e > a and s < b]

    def window_s(self) -> float | None:
        return None if self.job is None else (self.job[1] - self.job[0]) / 1e9

    def busy_s(self) -> dict:
        """Seconds some op ran, per device plane."""
        return {p: sum(e - s for s, e in _union(
            [(s, e) for s, e, _ in self._clip(evs)])) / 1e9
            for p, evs in self.ops.items() if evs}

    def idle_pct(self) -> dict:
        w = self.window_s()
        if not w:
            return {}
        return {p: 100.0 * (1.0 - b / w) for p, b in self.busy_s().items()}

    def program_s(self, patterns: list) -> float | None:
        """Summed device seconds of the programs matching ``patterns``
        (over all device planes); None when nothing matches."""
        pat = re.compile("|".join(patterns))
        hit = [(s, e) for evs in self.modules.values()
               for s, e, n in self._clip(evs) if pat.search(n)]
        return sum(e - s for s, e in hit) / 1e9 if hit else None

    def top_programs(self, k: int = 10) -> list:
        tot = {}
        for evs in self.modules.values():
            for s, e, n in self._clip(evs):
                tot[n] = tot.get(n, 0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / 1e9] for n, v in top]

    def idle_gaps(self, host_spans: list, offset_ns: int, k: int = 10) -> list:
        """The ``k`` longest gaps in the first device plane's busy time,
        each named by the innermost host span open at its midpoint.
        ``host_spans``: (name, start_ns, end_ns) on the host's clock;
        ``offset_ns`` maps it to the profiler's."""
        if not self.ops or self.job is None:
            return []
        plane = sorted(p for p, evs in self.ops.items() if evs)
        if not plane:
            return []
        busy = _union([(s, e) for s, e, _ in self._clip(self.ops[plane[0]])])
        a, b = self.job
        edges = [a] + [x for iv in busy for x in iv] + [b]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        spans = [(n, s + offset_ns, e + offset_ns) for n, s, e in host_spans]
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) // 2
            open_ = [(se - ss, n) for n, ss, se in spans if ss <= mid < se]
            label = min(open_)[1] if open_ else "no stage open"
            out.append([label, (e - s) / 1e9])
        return out
