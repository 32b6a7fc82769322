"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's operation intervals, their union (busy
time), per-operation sums, the time under each of the program's
``df2.*`` device scopes and under none, and the longest idle gaps with
what the host was doing in each.

Read with ``jax.profiler.ProfileData``, but for the scopes: they are in
each operation's ``tf_op`` path, a stat of the event's metadata, which
``ProfileData`` hides and ``xplane.py`` beside this file decodes. On a
TPU each chip is a plane ``/device:TPU:<n>`` whose ``XLA Ops`` line
holds one event per executed HLO operation; the host is the plane
``/host:CPU`` with one line per thread. A rehearsal on the CPU backend has no device plane:
there the operations run on XLA's own threads of the host plane
(``tf_XLA...``), and those lines stand in so that the reduction and the
readers can be rehearsed. Such a run is never a reading, and it has no
scope path: ``scope_seconds`` is empty there, ``scoped_s`` and
``unscoped_s`` are None, and their readers find nothing to read.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CPU_OP_LINES = "tf_XLA"
# Events that only frame others: they would name every gap and hide
# every operation.
_FRAMES = ("ThreadpoolListener", "ThunkExecutor", "SlinkyThreadPool",
           "end: ", "$")
# A scope in an operation's path. JAX wraps a path's first scope in the
# transformations it went through (``transpose(jvp(df2.model))``), so a
# scope is matched as a name, not as a whole path component.
SCOPE = re.compile(r"(?<![\w.])df2\.[A-Za-z_][\w.]*")


@dataclasses.dataclass
class Reduced:
    chips: int
    first_ns: float
    last_ns: float
    busy_s: float                 # union of op intervals, mean over chips
    op_seconds: dict              # op name -> summed seconds, mean over chips
    gaps: list                    # [(host event name, seconds)], longest first
    # scope -> seconds under it (the union of the intervals of the
    # operations whose path holds it: a ``while`` and the operations of
    # its body are both events, and a scope inside another counts for
    # both), the busy seconds under any scope and those under none (by
    # the same decoder, so the two add up to its busy time; ``busy_s``
    # is ``ProfileData``'s, which cuts every time to a whole ns); each a
    # mean over chips.
    scope_seconds: dict = dataclasses.field(default_factory=dict)
    scoped_s: float | None = None
    unscoped_s: float | None = None

    def seconds_where(self, selects) -> float:
        """Summed device seconds of the operations that ``selects(name)``
        picks; the name is the one the trace gives the operation (on a
        TPU the whole HLO instruction)."""
        return sum(s for name, s in self.op_seconds.items() if selects(name))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals) -> tuple[float, list]:
    """Total covered length and the gaps between covered stretches."""
    total, gaps = 0.0, []
    end = None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            if end is not None:
                gaps.append((end, start))
            total += stop - start
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total, gaps


def reduce(path: str, rehearse: bool = False, n_gaps: int = 5) -> Reduced:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    op_lines = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if not lines:
                raise ValueError(f"plane {plane.name} has no {OPS_LINE!r} "
                                 f"line: {[ln.name for ln in plane.lines]}")
            op_lines.append(lines)
    host = [p for p in planes if p.name == HOST_PLANE]
    if not op_lines and rehearse:
        op_lines = [[ln for p in host for ln in p.lines
                     if ln.name.startswith(CPU_OP_LINES)]]
    if not op_lines or not any(op_lines):
        raise ValueError("the trace holds no device operations: planes "
                         f"{[p.name for p in planes]}")

    busy, op_seconds = 0.0, {}
    all_gaps, first, last = [], None, None
    for lines in op_lines:
        intervals = []
        for line in lines:
            for ev in line.events:
                if ev.duration_ns <= 0 or ev.name.startswith(_FRAMES):
                    continue
                intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                op_seconds[ev.name] = (op_seconds.get(ev.name, 0.0)
                                       + ev.duration_ns * 1e-9)
        covered, gaps = _union(intervals)
        busy += covered * 1e-9
        all_gaps.extend(gaps)
        if intervals:
            lo = min(i[0] for i in intervals)
            hi = max(i[1] for i in intervals)
            first = lo if first is None else min(first, lo)
            last = hi if last is None else max(last, hi)
    chips = len(op_lines)
    op_seconds = {k: v / chips for k, v in op_seconds.items()}

    host_events = []
    for plane in host:
        for line in plane.lines:
            if line.name.startswith(("tf_XLA", "tf_pjrt")):
                continue
            for ev in line.events:
                if ev.duration_ns > 0 and not ev.name.startswith(_FRAMES):
                    host_events.append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    gaps = [(_host_in(host_events, a, b), (b - a) * 1e-9) for a, b in longest]
    return Reduced(chips, first, last, busy / chips, op_seconds, gaps,
                   *scopes(path))


def scopes(path: str) -> tuple[dict, float | None, float | None]:
    """``Reduced``'s ``scope_seconds``, ``scoped_s`` and ``unscoped_s``
    of the dump at ``path``, as ``df2-trace-tool train`` reckons a
    scope's time. Without a device plane: ``({}, None, None)``."""
    from benchmarks import xplane

    under, inside, bare, chips = {}, 0.0, 0.0, 0
    for plane in xplane.read_xspace(path):
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        chips += 1
        every, scoped, by_scope = [], [], {}
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                every.append(span)
                found = set(SCOPE.findall(ev.stats.get("tf_op") or ""))
                if found:
                    scoped.append(span)
                for name in found:
                    by_scope.setdefault(name, []).append(span)
        for name, spans in by_scope.items():
            under[name] = under.get(name, 0.0) + _union(spans)[0] * 1e-9
        covered = _union(scoped)[0]
        inside += covered * 1e-9
        bare += (_union(every)[0] - covered) * 1e-9
    if not chips:
        return {}, None, None
    return ({k: v / chips for k, v in under.items()}, inside / chips,
            bare / chips)


def _host_in(host_events, a: float, b: float) -> str:
    """The shortest host event that covers at least half of the gap."""
    best, best_len = "no host event", None
    for start, stop, name in host_events:
        overlap = min(stop, b) - max(start, a)
        if overlap * 2 >= (b - a) and (best_len is None
                                       or stop - start < best_len):
            best, best_len = name, stop - start
    return best


def breakdown(reduced: Reduced, selects=None, n_ops: int = 10,
              name_chars: int = 160) -> dict:
    """The contract's ``breakdown``: the device operations with most
    time under the names the trace gives them (cut to ``name_chars``),
    and the longest idle gaps by what the host was doing. A loop and
    the operations of its body are both events, so the entries may
    nest. With ``selects`` (a kernel metric's selector) the last entry
    is ``other``: the device's busy time outside the operations it
    picks, so a renamed kernel shows instead of vanishing."""
    ops = sorted(reduced.op_seconds.items(), key=lambda kv: -kv[1])
    top = [[name[:name_chars], secs] for name, secs in ops[:n_ops]]
    if selects is not None:
        other = reduced.busy_s - reduced.seconds_where(selects)
        top = top[:n_ops - 1] + [["other", other]]
    return {"device_ops": top,
            "idle_gaps": [[name, secs] for name, secs in reduced.gaps]}
