"""The program's layers in a profiler trace: device time by named scope,
and device idle time under the program's own host spans.

The program opens a ``jax.named_scope`` around each layer (``SCOPES``).
XLA keeps the scope path as each op's ``op_name`` metadata, and a TPU
trace carries it as the ``tf_op`` stat of the op's event metadata, for
example ``jit(train_step)/transpose(jvp(embedding))/scatter-add:``.
``jax.profiler.ProfileData`` does not expose event metadata, so
``read_tf_ops`` reads the XSpace protobuf's wire format itself: nothing
beyond the standard library.

An op belongs to the innermost of the known scopes on its path, and is
backward where that scope, or a scope around it, sits inside
``transpose(``: JAX names the transposed (backward) half of a ``jvp``
that way on every backend.

An op whose interval holds other ops of its plane (a ``while`` loop, as
the TPU compiler makes of a scatter, lists its body's ops inside its own
interval, and carries no ``tf_op`` itself) counts only the time that none
of them covers, under the scope of the ops it holds: nothing is counted
twice, and the scope sums add up to the busy time.

The program's host spans (``jax.profiler.TraceAnnotation``: ``serve.h2d``,
``serve.run``, ``serve.d2h``, ``train.h2d``, ``train.run``,
``train.sync``) land in the host plane beside the benchmark's own
(``trace.HOST_LABELS``); ``ScopedTrace.span_idle_s`` gives the device idle
time that the spans of one name cover, and ``input_wait_s`` the idle time
in which a program waited for its input, on the host's clock
(``skew_ns``).

``keep_scopes(run)``, a per-layer metric's ``warm``, has the window trace
of a traced run load as a ``ScopedTrace``, which carries the ``tf_op`` of
each op beside everything ``trace.Trace`` holds.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from bench import trace as trace_lib
from bench.trace import Trace, _intervals, overlap, union

#: the program's device scopes, one per layer
SCOPES = ("embedding", "bottom_mlp", "interaction", "top_mlp", "optimizer")
#: the layers of the dense model, forward and backward
DENSE = ("bottom_mlp", "interaction", "top_mlp")
#: the program's host spans
PROGRAM_SPANS = ("serve.h2d", "serve.run", "serve.d2h",
                 "train.h2d", "train.run", "train.sync")

_NAME = re.compile(r"[A-Za-z_][\w.\-]*")


# -- the XSpace wire format -----------------------------------------------

def _varint(b: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
            yield field, value
        elif wire == 2:
            size, i = _varint(b, i)
            yield field, b[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _map_entry(b: memoryview) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_tf_ops(plane: memoryview) -> Tuple[str, Dict[str, str]]:
    """(plane name, {op metadata name: tf_op}) of one XPlane."""
    name = ""
    stat_names: Dict[int, str] = {}
    events = []
    for f, v in _fields(plane):
        if f == 2:                          # XPlane.name
            name = bytes(v).decode()
        elif f == 4:                        # XPlane.event_metadata
            events.append(_map_entry(v)[1])
        elif f == 5:                        # XPlane.stat_metadata
            key, meta = _map_entry(v)
            for g, w in _fields(meta):
                if g == 2:                  # XStatMetadata.name
                    stat_names[key] = bytes(w).decode()
    tf_op_ids = {k for k, n in stat_names.items() if n == "tf_op"}
    out: Dict[str, str] = {}
    if not tf_op_ids:
        return name, out
    for meta in events:
        op, path = "", ""
        for f, v in _fields(meta):
            if f == 2:                      # XEventMetadata.name
                op = bytes(v).decode()
            elif f == 5:                    # XEventMetadata.stats
                stat = dict(_fields(v))
                if stat.get(1) in tf_op_ids:
                    if 5 in stat:           # XStat.str_value
                        path = bytes(stat[5]).decode()
                    elif 7 in stat:         # XStat.ref_value
                        path = stat_names.get(stat[7], "")
        if op and path:
            out[op] = path
    return name, out


def read_tf_ops(data: bytes) -> Dict[str, Dict[str, str]]:
    """{plane name: {op name: tf_op}} of a serialized XSpace, for every
    plane whose ops carry a ``tf_op``.  The op name is the event metadata's
    name, which is what ``trace.Trace`` names each operation."""
    out: Dict[str, Dict[str, str]] = {}
    for f, v in _fields(memoryview(data)):
        if f == 1:                          # XSpace.planes
            name, ops = _plane_tf_ops(v)
            if ops:
                out.setdefault(name, {}).update(ops)
    return out


def load_tf_ops(trace_dir: str) -> Dict[str, Dict[str, str]]:
    """``read_tf_ops`` of every ``*.xplane.pb`` under ``trace_dir``."""
    out: Dict[str, Dict[str, str]] = defaultdict(dict)
    for path in sorted(glob.glob(os.path.join(trace_dir, "**",
                                              "*.xplane.pb"),
                                 recursive=True)):
        with open(path, "rb") as f:
            for plane, ops in read_tf_ops(f.read()).items():
                out[plane].update(ops)
    return dict(out)


# -- scopes ---------------------------------------------------------------

def scope_of(tf_op: str, scopes: Sequence[str] = SCOPES
             ) -> Tuple[Optional[str], bool]:
    """(the innermost of ``scopes`` on the op's path or None, whether the
    op is backward: that scope, or one around it, inside ``transpose(``)."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    found, backward, transposed = None, False, False
    for part in path.split("/"):
        names = _NAME.findall(part)
        transposed = transposed or "transpose" in names[:-1]
        if names and names[-1] in scopes:
            found, backward = names[-1], transposed
    return found, backward


def _attributed(events: Sequence[Tuple[str, float, float]],
                paths: Dict[str, str]) -> List[Tuple[str, float, Tuple]]:
    """[(op name, ns, (scope, backward))] for the ops of one plane: each op
    counts the time that no op inside its interval covers, under its own
    scope, or, where it has none and holds other ops, under the scope that
    holds most of their time."""
    order = sorted(range(len(events)),
                   key=lambda k: (events[k][1], -events[k][2]))
    parent = [-1] * len(events)
    stack: List[int] = []
    for k in order:
        _, s, d = events[k]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack and s + d <= events[stack[-1]][1] + events[stack[-1]][2]:
            parent[k] = stack[-1]
        stack.append(k)
    own = [float(d) for _, _, d in events]
    held: List[Dict[Tuple, float]] = [defaultdict(float) for _ in events]
    scope = [scope_of(paths.get(name, "")) for name, _, _ in events]
    # children start after their parent: settle them first
    for k in reversed(order):
        if held[k] and scope[k][0] is None:
            scope[k] = max(held[k].items(), key=lambda kv: kv[1])[0]
        p = parent[k]
        if p >= 0 and events[k][2] > 0:
            own[p] -= events[k][2]
            held[p][scope[k]] += events[k][2]
    return [(events[k][0], max(own[k], 0.0), scope[k])
            for k in range(len(events))]


class ScopedTrace(Trace):
    """A ``Trace`` that also knows each operation's scope path."""

    def __init__(self, ops, modules, host,
                 tf_op: Optional[Dict[str, Dict[str, str]]] = None):
        super().__init__(ops, modules, host)
        self.tf_op = tf_op or {}      # device plane -> {op name: tf_op}

    @classmethod
    def load(cls, trace_dir: str) -> "ScopedTrace":
        tr = super().load(trace_dir)
        tr.tf_op = load_tf_ops(trace_dir)
        return tr

    def attributed(self) -> Iterator[Tuple[str, str, float, Tuple]]:
        """(plane, op name, seconds averaged over devices, (scope,
        backward)) of every operation, as ``_attributed`` counts it."""
        for plane, events in self.ops.items():
            for name, ns, scope in _attributed(events,
                                               self.tf_op.get(plane, {})):
                yield plane, name, ns * 1e-9 / len(self.ops), scope

    def scope_s(self, scopes: Sequence[str],
                backward: Optional[bool] = None) -> Optional[float]:
        """Device seconds of the operations under any of ``scopes``
        (summed over runs, averaged over devices); only the forward ones
        with ``backward=False``, only the backward ones with ``True``.
        None where no operation lies under them."""
        total, hit = 0.0, False
        for _, _, t, (scope, bwd) in self.attributed():
            if scope in scopes and backward in (None, bwd):
                total += t
                hit = True
        return total if hit else None

    def _busy(self, plane: str) -> np.ndarray:
        """The plane's busy intervals on the host's clock."""
        return union(_intervals(self.ops[plane])) + self.skew_ns(plane)

    def span_idle_s(self, label: str) -> Optional[float]:
        """Device idle seconds covered by the host spans named ``label``
        (their length less the device's busy time within them, on the
        host's clock), on the first device.  None without such spans."""
        spans = union(_intervals([e for e in self.host if e[0] == label]))
        if not len(spans) or not self.ops:
            return None
        busy = self._busy(sorted(self.ops)[0])
        length = float((spans[:, 1] - spans[:, 0]).sum())
        return (length - overlap(spans, busy)) * 1e-9

    def input_wait_s(self, h2d: str, dispatch: str,
                     program: str) -> Optional[float]:
        """Device idle seconds in which the runs of the programs whose name
        holds ``program`` waited for their input, on the first device: the
        idle under the ``h2d`` spans (the host makes the batch and starts
        its copy), and before each run the idle from the end of its
        ``dispatch`` span (the last to start before the run did) or of the
        run before, whichever is later, to the run's start: ``jnp.asarray``
        returns before the copy ends, and a run waits for its inputs.  On
        the host's clock; None without ``dispatch`` spans or such runs."""
        if not self.ops:
            return None
        plane = sorted(self.ops)[0]
        skew = self.skew_ns(plane)
        runs = union(_intervals([m for m in self.modules.get(plane, [])
                                 if program in m[0]])) + skew
        spans = sorted((s, s + d) for n, s, d in self.host if n == dispatch)
        if not len(runs) or not spans:
            return None
        starts = np.array([s for s, _ in spans])
        waits, prev = [], -np.inf
        for lo, hi in runs:
            k = int(np.searchsorted(starts, lo, side="right")) - 1
            if k >= 0:
                begin = max(spans[k][1], prev)
                if begin < lo:
                    waits.append((begin, lo))
            prev = hi
        after = 0.0
        if waits:
            waits = np.array(waits)
            after = float((waits[:, 1] - waits[:, 0]).sum()) - overlap(
                waits, self._busy(plane))
        return (self.span_idle_s(h2d) or 0.0) + after * 1e-9


# -- what the metrics read --------------------------------------------------

def keep_scopes(run) -> None:
    """Have the window trace of ``run`` load as a ``ScopedTrace`` (nothing
    in a run that is not traced).  A per-layer metric's ``warm``."""
    if not run.trace or getattr(run, "keeps_scopes", False):
        return
    stop = run.stop_trace

    def stop_trace(name: str = "window") -> None:
        if name != "window":
            return stop(name)
        plain = trace_lib.Trace
        trace_lib.Trace = ScopedTrace
        try:
            stop(name)
        finally:
            trace_lib.Trace = plain

    run.stop_trace = stop_trace
    run.keeps_scopes = True


def _window(run, per: str):
    tr = run.traces.get("window")
    n = run.record.get(per)
    if not isinstance(tr, ScopedTrace) or not n:
        return None, 0
    return tr, n


def scope_ms(run, scopes: Sequence[str], per: str,
             backward: Optional[bool] = None) -> Optional[float]:
    """Device ms under ``scopes`` in the traced window, per one of
    ``run.record[per]`` (the window's calls or steps)."""
    tr, n = _window(run, per)
    s = tr.scope_s(scopes, backward) if tr is not None else None
    return None if s is None else s / n * 1e3


def input_wait_ms(run, h2d: str, dispatch: str, program: str,
                  per: str) -> Optional[float]:
    """``ScopedTrace.input_wait_s`` of the traced window in ms, per one of
    ``run.record[per]``."""
    tr, n = _window(run, per)
    s = tr.input_wait_s(h2d, dispatch, program) if tr is not None else None
    return None if s is None else s / n * 1e3


def by_scope(tr: ScopedTrace) -> Dict[str, float]:
    """{scope, or scope + " bwd", or "unscoped": device seconds}."""
    out: Dict[str, float] = defaultdict(float)
    for _, _, t, (scope, bwd) in tr.attributed():
        key = "unscoped" if scope is None else scope + (" bwd" if bwd
                                                        else "")
        out[key] += t
    return dict(out)


def top_unscoped(tr: ScopedTrace, n: int) -> list:
    """[[op name, tf_op, seconds], ...]: the costliest operations under no
    scope."""
    tot: Dict[Tuple[str, str], float] = defaultdict(float)
    for plane, name, t, (scope, _) in tr.attributed():
        if scope is None:
            tot[(name[:160], tr.tf_op.get(plane, {}).get(name, ""))] += t
    return [[k[0], k[1], v] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
