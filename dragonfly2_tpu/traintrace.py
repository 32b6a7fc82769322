"""What ``df2-trace-tool train`` prints: one profiler dump of a train
loop (``df2-trainer --profile-dir``, or a benchmark cell's
``.bench_trace/<cell>/``) split by the names the program gives its own
phases (docs/OBSERVABILITY.md "Training loops").

- Per device: the time per step under each ``df2.*`` scope and under
  none. A scope's time is the *union* of the intervals of the ``XLA
  Ops`` events whose ``tf_op`` path holds the scope, because a ``while``
  and the operations of its body are both events; ``self`` counts an
  event only for the innermost scope of its path (``df2.attn.gather``
  lies inside ``df2.model``). JAX wraps a path's first scope in the
  transformations it went through (``transpose(jvp(df2.model))``), so a
  scope is matched as a name, not as a whole path component.
  Operations the compiler makes itself (layout copies, the loops a
  reshape is turned into) carry no path at all; for those the tool
  says which scope's operations ran next on the device, which is
  adjacency, not attribution.
- Per host thread: the ``df2.train.*`` spans' totals per step, and
  the ``df2.setup.*`` spans (a trainer's set-up phases, before its loop:
  ``data``, ``state``, ``tables``) with their totals alone.
- ``step_facts``: what the loop wrote on its ``df2.train.step`` spans
  besides the step's number (``sampler_row_width``: the lanes of
  GraphSAGE's per-host neighbour rows, 0 on the CSR sampler;
  ``seq_attn_window``: the window of a sequence model's sliding layers,
  0 where it has none, whose kernel is ``df2.seq.attn_window``;
  ``seq_sparse_topk``: the keys a query keeps where attention runs over
  a learned selection, 0 where it does not, whose scopes are
  ``df2.seq.index``, ``df2.seq.select`` and ``df2.seq.attn_sparse``;
  ``seq_loop_steps``: how many times a looped sequence model runs its
  layers, 0 where it runs them once, whose exits are ``df2.seq.exit``;
  ``seq_head_fused_blocks``: the blocks of positions in which a
  sequence's loss head forms its gradient in its forward pass, under
  ``df2.loss`` or ``df2.seq.exit``).
- The longest device idle gaps, each with the ``df2.train.*`` or
  ``df2.setup.*`` span the loop's thread was in.

A CPU trace names its operations by ``hlo_op`` alone, with no scope
path: there the device part is empty and the host part still reads.
"""

from __future__ import annotations

import bisect
import re

from dragonfly2_tpu.utils.xplane import find_xplane, read_xspace

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN = "df2.train."
SETUP_SPAN = "df2.setup."
_SPANS = (HOST_SPAN, SETUP_SPAN)
STEP_SPAN = "df2.train.step"
_SCOPE = re.compile(r"(?<![\w.])df2\.[A-Za-z_][\w.]*")
NO_SPAN = "no df2.train span"
NO_SCOPE = "(nothing scoped)"


def scopes_of(tf_op: str, scope=_SCOPE) -> list:
    """The scopes in an operation's path, outermost first (``scope``: a
    compiled pattern; the ``df2.*`` names unless told otherwise)."""
    return scope.findall(tf_op or "")


def union_ns(intervals) -> tuple:
    """Covered length of a set of (start, stop), and the gaps between
    covered stretches."""
    total, gaps, end = 0.0, [], None
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


def _loop_line(host_lines):
    """The loop's thread is the one that holds the ``df2.train.step``
    spans (thread names need not reach the profiler)."""
    best, most = None, 0
    for line in host_lines:
        n = sum(ev.name == STEP_SPAN for ev in line.events)
        if n > most:
            best, most = line, n
    return best


def _span_in(spans, a: float, b: float) -> str:
    """The shortest span that covers at least half of the gap."""
    best, best_len = NO_SPAN, None
    for start, stop, name in spans:
        overlap = min(stop, b) - max(start, a)
        if overlap * 2 >= (b - a) and (best_len is None
                                       or stop - start < best_len):
            best, best_len = name, stop - start
    return best


def _device(plane, loop_spans, host_steps: int, n_gaps: int,
            n_unscoped: int, scope) -> dict:
    ops = [ev for line in plane.lines if line.name == OPS_LINE
           for ev in line.events if ev.duration_ns > 0]
    # One execution of the step program is one event of the module line;
    # the program with most time is the step.
    by_module = {}
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for ev in line.events:
                count, total = by_module.get(ev.name, (0, 0.0))
                by_module[ev.name] = (count + 1, total + ev.duration_ns)
    module, (steps, _) = max(by_module.items(), key=lambda kv: kv[1][1],
                             default=("", (host_steps, 0.0)))
    steps = max(steps, 1)

    whole, self_only, scoped, bare = {}, {}, [], []
    every = []
    for ev in ops:
        span = (ev.start_ns, ev.start_ns + ev.duration_ns)
        every.append(span)
        found = scopes_of(ev.stats.get("tf_op"), scope)
        if not found:
            bare.append((span, ev.name))
            continue
        scoped.append((span, found[-1]))
        for name in set(found):
            whole.setdefault(name, []).append(span)
        self_only.setdefault(found[-1], []).append(span)
    busy, gaps = union_ns(every)
    under_any, _ = union_ns(span for span, _ in scoped)

    # An operation without a path, by the scope that ran next.
    scoped.sort()
    starts = [span[0] for span, _ in scoped]
    unscoped, before = {}, {}
    for span, name in bare:
        i = bisect.bisect_left(starts, span[1])
        follows = scoped[i][1] if i < len(scoped) else NO_SCOPE
        total, nexts = unscoped.setdefault(name, [0.0, {}])
        unscoped[name][0] = total + span[1] - span[0]
        nexts[follows] = nexts.get(follows, 0) + 1
        before.setdefault(follows, []).append(span)

    def per_step(ns: float) -> float:
        return ns * 1e-6 / steps

    scopes = {}
    for name in sorted(whole):
        covered, _ = union_ns(whole[name])
        alone, _ = union_ns(self_only.get(name, ()))
        scopes[name] = {
            "ms_per_step": per_step(covered),
            "self_ms_per_step": per_step(alone),
            "summed_ms_per_step": per_step(
                sum(b - a for a, b in whole[name])),
            "share_of_busy": covered / busy if busy else 0.0,
            "events": len(whole[name])}
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    first = min((s for s, _ in every), default=0.0)
    return {
        "plane": plane.name,
        "step_program": module,
        "steps": steps,
        "busy_ms": busy * 1e-6,
        "busy_ms_per_step": per_step(busy),
        "scoped_share_of_busy": under_any / busy if busy else 0.0,
        "scopes": scopes,
        "unscoped_ms_per_step": per_step(busy - under_any),
        # Summed, not a union: a loop outside every scope (the step's own
        # ``while``) and its body both count, so read these as names.
        "unscoped_ops": [
            {"op": name[:160], "summed_ms_per_step": per_step(ns),
             "runs_before": max(nexts, key=nexts.get)}
            for name, (ns, nexts) in sorted(
                unscoped.items(), key=lambda kv: -kv[1][0])[:n_unscoped]],
        # The union of the unscoped intervals, by the scope that ran
        # next: adjacency on the device's timeline, not attribution.
        "unscoped_ms_per_step_before": {
            name: per_step(union_ns(spans)[0])
            for name, spans in sorted(before.items())},
        "idle_gaps": [
            {"ms": (b - a) * 1e-6, "at_ms": (a - first) * 1e-6,
             "host_span": _span_in(loop_spans, a, b)} for a, b in longest],
    }


def _thread(line, index: int, is_loop: bool, steps: int) -> dict:
    spans, setup = {}, {}
    for ev in line.events:
        if ev.name.startswith(_SPANS):
            entry = (spans if ev.name.startswith(HOST_SPAN)
                     else setup).setdefault(
                ev.name, {"count": 0, "total_ms": 0.0})
            entry["count"] += 1
            entry["total_ms"] += ev.duration_ns * 1e-6
    for entry in spans.values():
        entry["ms_per_step"] = entry["total_ms"] / max(steps, 1)
    return {"thread": f"{line.name} #{index}", "loop": is_loop,
            "spans": dict(sorted(spans.items())),
            "setup": dict(sorted(setup.items()))}


def analyze(where: str, n_gaps: int = 5, n_unscoped: int = 5,
            scope=_SCOPE) -> dict:
    """``scope``: the compiled pattern of a scope's name in ``tf_op``
    (for a dump of a build without the ``df2.*`` scopes, a flax module
    path does as well)."""
    path = find_xplane(where)
    return {"path": path, **analyze_planes(
        read_xspace(path), n_gaps, n_unscoped, scope)}


def analyze_planes(planes, n_gaps: int = 5, n_unscoped: int = 5,
                   scope=_SCOPE) -> dict:
    host_lines = [line for plane in planes if plane.name == HOST_PLANE
                  for line in plane.lines
                  if any(ev.name.startswith(_SPANS)
                         for ev in line.events)]
    loop = _loop_line(host_lines)
    host_steps = (sum(ev.name == STEP_SPAN for ev in loop.events)
                  if loop is not None else 0)
    loop_spans = [] if loop is None else [
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
        for ev in loop.events if ev.name.startswith(_SPANS)]
    facts = {}
    for ev in (loop.events if loop is not None else ()):
        if ev.name == STEP_SPAN:
            facts.update((k, v) for k, v in ev.stats.items()
                         if k != "step_num" and not k.startswith("_"))
    return {
        "host_steps": host_steps,
        "step_facts": facts,
        "devices": [
            _device(plane, loop_spans, host_steps, n_gaps, n_unscoped, scope)
            for plane in planes if plane.name.startswith(DEVICE_PLANE)],
        "threads": [
            _thread(line, i, line is loop, host_steps)
            for i, line in enumerate(host_lines)],
    }


def format_report(report: dict) -> str:
    out = [f"trace      {report['path']}",
           f"host steps {report['host_steps']} (df2.train.step spans on "
           "the loop's thread)"]
    out += [f"{name} {value}"
            for name, value in report["step_facts"].items()]
    if not report["devices"]:
        out.append("no device plane: a CPU trace carries no scope paths")
    for dev in report["devices"]:
        out.append("")
        out.append(
            f"{dev['plane']}  {dev['steps']} x {dev['step_program']}  busy "
            f"{dev['busy_ms_per_step']:.3f} ms/step, "
            f"{100 * dev['scoped_share_of_busy']:.1f}% under the scopes")
        out.append(f"  {'scope':28} {'ms/step':>10} {'self':>10} "
                   f"{'of busy':>8}")
        for name, s in dev["scopes"].items():
            out.append(f"  {name:28} {s['ms_per_step']:10.3f} "
                       f"{s['self_ms_per_step']:10.3f} "
                       f"{100 * s['share_of_busy']:7.1f}%")
        out.append(f"  {'(no scope)':28} {dev['unscoped_ms_per_step']:10.3f}")
        for name, ms in dev["unscoped_ms_per_step_before"].items():
            if ms >= 0.01 * dev["unscoped_ms_per_step"]:
                out.append(f"    {ms:9.3f} ms/step of operations without a "
                           f"path ran just before {name}")
        for op in dev["unscoped_ops"]:
            out.append(f"    {op['summed_ms_per_step']:9.3f} ms/step  "
                       f"[before {op['runs_before']}]  {op['op'][:90]}")
        out.append("  longest idle gaps:")
        for gap in dev["idle_gaps"]:
            out.append(f"    {gap['ms']:9.4f} ms at {gap['at_ms']:10.2f} ms  "
                       f"{gap['host_span']}")
    for thread in report["threads"]:
        out.append("")
        out.append(f"thread {thread['thread']}"
                   + ("  (the loop)" if thread["loop"] else ""))
        for name, s in thread["setup"].items():
            out.append(f"  {name:24} {s['count']:6d} x  "
                       f"{s['total_ms']:10.3f} ms  (set-up)")
        for name, s in thread["spans"].items():
            out.append(f"  {name:24} {s['count']:6d} x  "
                       f"{s['total_ms']:10.3f} ms  "
                       f"{s['ms_per_step']:9.4f} ms/step")
    return "\n".join(out)
