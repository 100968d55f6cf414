"""Reduction of a profiler trace to the numbers the benchmark reports.

A trace is the ``*.xplane.pb`` that ``jax.profiler`` writes.  From it the
benchmark keeps three lists of (name, start ns, duration ns):

* device operations: the events of each device plane's ``XLA Ops`` line;
* device programs: the events of its ``XLA Modules`` line, one per run of
  a compiled program, named after the jitted function;
* host spans: every event of the host plane, among them the benchmark's
  own ``jax.profiler.TraceAnnotation`` spans (``HOST_LABELS``).

``busy_s`` is the union of the operation intervals of a device, averaged
over the devices; the idle share of a window is one minus busy over its
length.  An idle gap is a stretch between two busy intervals; its time
goes to each host label whose spans cover it, and the rest to ``other``.

The device's clock and the host's disagree by some milliseconds in a v5e
trace (program runs that end before the host launched them).  ``skew_ns``
estimates the difference from the host's ``tpu::System::Execute=>Done``
events, each of which follows the end of one program run, and idle gaps
are shifted by it before they are labelled.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: the benchmark's own host spans, by which idle gaps are labelled
HOST_LABELS = ("score", "batch_at", "step")
#: the host event that follows the end of each program run on a TPU
DONE = "tpu::System::Execute=>Done"

Event = Tuple[str, float, float]          # name, start ns, duration ns


def union(intervals: np.ndarray) -> np.ndarray:
    """Disjoint sorted [n, 2] (start, end) covering ``intervals``."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new run starts where an interval begins after every earlier end
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    idx = np.flatnonzero(new)
    starts = iv[idx, 0]
    run_end = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[run_end]], axis=1)


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint sorted sets."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i, 0], b[j, 0])
        hi = min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def _intervals(events: Sequence[Event]) -> np.ndarray:
    if not events:
        return np.zeros((0, 2))
    a = np.array([(s, s + d) for _, s, d in events], np.float64)
    return a


class Trace:
    def __init__(self, ops: Dict[str, List[Event]],
                 modules: Dict[str, List[Event]], host: List[Event]):
        self.ops = ops              # device plane name -> operations
        self.modules = modules      # device plane name -> program runs
        self.host = host

    @classmethod
    def load(cls, trace_dir: str) -> "Trace":
        from jax.profiler import ProfileData
        paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
        ops, modules, host = defaultdict(list), defaultdict(list), []
        for path in paths:
            with open(path, "rb") as f:
                pd = ProfileData.from_serialized_xspace(f.read())
            for plane in pd.planes:
                if plane.name.startswith("/device:") and \
                        not plane.name.startswith("/device:CPU"):
                    for line in plane.lines:
                        if line.name == "XLA Ops":
                            ops[plane.name] += [(e.name, e.start_ns,
                                                 e.duration_ns)
                                                for e in line.events]
                        elif line.name == "XLA Modules":
                            modules[plane.name] += [(e.name, e.start_ns,
                                                     e.duration_ns)
                                                    for e in line.events]
                elif plane.name.startswith("/host:"):
                    for line in plane.lines:
                        host += [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
        return cls(dict(ops), dict(modules), host)

    # -- device time ---------------------------------------------------------

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        per = []
        for events in self.ops.values():
            u = union(_intervals(events))
            per.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        return float(np.mean(per))

    def top_ops(self, n: int) -> list:
        """[[name, seconds], ...]: the operations that took most device time
        (summed over runs, averaged over devices), each named by the first
        160 characters of its HLO (name, result shape, operation)."""
        tot: Dict[str, float] = defaultdict(float)
        for events in self.ops.values():
            for name, _, d in events:
                tot[name[:160]] += d * 1e-9 / len(self.ops)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]

    def module_runs(self, name: str) -> List[float]:
        """Device seconds of each run of the programs whose name holds
        ``name``, on the first device that ran one."""
        for plane in sorted(self.modules):
            runs = [d * 1e-9 for m, _, d in self.modules[plane] if name in m]
            if runs:
                return runs
        return []

    # -- idle gaps -----------------------------------------------------------

    def skew_ns(self, plane: str) -> float:
        """Host time minus device time: the median gap from each program
        run's end to its ``Done`` event, pairing runs and events in order;
        where the trace's edges cut one list shorter, of the pairings that
        use all of the shorter list, the one that agrees best."""
        ends = sorted(s + d for _, s, d in self.modules.get(plane, []))
        done = sorted(s for n, s, _ in self.host if n == DONE)
        n = min(len(ends), len(done))
        best = None
        for k in range(-(len(ends) - n), len(done) - n + 1) if n else ():
            # runs ends[i], events done[i + k], for all i that exist
            diff = np.array([done[i + k] - e for i, e in enumerate(ends)
                             if 0 <= i + k < len(done)])
            if len(diff) < n:
                continue
            med = float(np.median(diff))
            spread = float(np.median(np.abs(diff - med)))
            if best is None or spread < best[0]:
                best = (spread, med)
        return best[1] if best else 0.0

    def idle_by_label(self, labels: Sequence[str], n: int) -> list:
        """[[label, seconds], ...]: device idle time between busy intervals,
        by the host span that covers it, longest first."""
        if not self.ops:
            return []
        plane = sorted(self.ops)[0]
        busy = union(_intervals(self.ops[plane]))
        if len(busy) < 2:
            return []
        gaps = np.stack([busy[:-1, 1], busy[1:, 0]], axis=1)
        gaps = gaps + self.skew_ns(plane)
        total = float((gaps[:, 1] - gaps[:, 0]).sum())
        out = {}
        covered = 0.0
        for label in labels:
            spans = union(_intervals([e for e in self.host if e[0] == label]))
            t = overlap(gaps, spans)
            if t > 0:
                out[label] = t * 1e-9
                covered += t
        out["other"] = max(0.0, total - covered) * 1e-9
        return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])
                [:n] if v > 0]
