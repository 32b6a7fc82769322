"""``df2-trace-tool`` — critical-path analysis of swarm span traces.

Usage::

    df2-trace-tool analyze TRACE_DIR [TRACE_DIR...]   # slowest first
    df2-trace-tool analyze --task-id T --json DIR     # one task, JSON
    df2-trace-tool list DIR                           # one line per task
    df2-trace-tool train DUMP_DIR [--json]            # a train loop's profile

``analyze`` and ``list`` read the rotated ``trace-*.jsonl`` files every service writes under
``--trace-dir`` (tail-sampled: SLO-breaching tasks are always present),
stitches spans by trace id, and names each task's dominant critical-path
contributor (docs/OBSERVABILITY.md). ``train`` reads a JAX profiler
dump of a train loop (``df2-trainer --profile-dir``, or a benchmark
cell's ``.bench_trace/<cell>/``): device time per step under each
``df2.*`` scope, the ``df2.train.*`` host spans per thread, and the
longest device idle gaps by the span the loop was in
(docs/OBSERVABILITY.md "Training loops").
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser("df2-trace-tool")
    sub = parser.add_subparsers(dest="command", required=True)
    for name in ("analyze", "list"):
        p = sub.add_parser(name)
        p.add_argument("paths", nargs="+",
                       help="trace dirs (or span JSONL files)")
        p.add_argument("--task-id", default="",
                       help="only traces of this task id (prefix ok)")
        p.add_argument("--json", action="store_true",
                       help="machine-readable output")
        p.add_argument("--limit", type=int, default=0,
                       help="at most N traces (0 = all)")
    p = sub.add_parser("train")
    p.add_argument("dump", help="profile dir (its newest *.xplane.pb is "
                                "read) or one .xplane.pb")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    p.add_argument("--scope", default="",
                   help="pattern of a scope's name in an operation's path "
                        "(default: the df2.* scopes)")
    args = parser.parse_args(argv)

    if args.command == "train":
        import re

        from dragonfly2_tpu import traintrace

        more = {"scope": re.compile(args.scope)} if args.scope else {}
        report = traintrace.analyze(args.dump, **more)
        print(json.dumps(report, indent=2) if args.json
              else traintrace.format_report(report))
        return 0

    from dragonfly2_tpu.tracetool import analyze_dirs, format_report

    reports = analyze_dirs(args.paths)
    if args.task_id:
        reports = [r for r in reports
                   if r["task_id"].startswith(args.task_id)]
    if args.limit > 0:
        reports = reports[:args.limit]
    if args.command == "list":
        if args.json:
            print(json.dumps([{k: r[k] for k in (
                "trace_id", "task_id", "peer_id", "ttlb_s", "success",
                "tail_reason")} for r in reports], indent=2))
        else:
            for r in reports:
                print(f"{r['trace_id']}  ttlb={r['ttlb_s']:8.3f}s  "
                      f"success={r['success']!s:5}  "
                      f"dominant={r['dominant']['kind']:13}  "
                      f"task={r['task_id'][:32]}")
        return 0
    if args.json:
        print(json.dumps(reports, indent=2))
    else:
        for r in reports:
            print(format_report(r))
            print()
    if not reports:
        print("no task traces found", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
