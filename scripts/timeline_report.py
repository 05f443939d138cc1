#!/usr/bin/env python
"""Render / validate a pipeline Chrome-trace file (the observatory's
offline half).

    python scripts/timeline_report.py trace.json           # blame table
                                                           # + verdict
    python scripts/timeline_report.py trace.json --json    # machine-
                                                           # readable
    python scripts/timeline_report.py trace.json --check   # schema
                                                           # validation
                                                           # only

The trace comes from ``GET /debug/timeline?format=chrome`` on a live
process (``observability/timeline.py`` ``dump_chrome_trace`` writes the
same file, but no program calls it since ``bench.py`` went); Perfetto
(https://ui.perfetto.dev) loads the same file directly.  ``--check``
validates against the trace-event schema subset we emit (complete 'X'
events with numeric non-negative ts/dur, matched 'B'/'E' pairs with
per-(pid,tid) monotonic timestamps) and exits nonzero on any violation
(``tests/test_timeline.py`` runs it over a trace it records).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from kyverno_tpu.observability import timeline  # noqa: E402


def check(trace) -> int:
    errors = timeline.validate_chrome_trace(trace)
    if errors:
        for e in errors:
            print(e, file=sys.stderr)
        print(f'{len(errors)} schema violation(s)', file=sys.stderr)
        return 1
    events = trace.get('traceEvents', []) if isinstance(trace, dict) \
        else trace
    print(f'ok: {len(events)} trace events')
    return 0


def report(trace, as_json: bool) -> int:
    summary = timeline.blame_from_chrome(trace)
    if as_json:
        print(json.dumps(summary, indent=2))
        return 0
    totals = summary['blame_s']
    if not totals:
        print('no exec events in trace')
        return 1
    print(f'{len(summary["scans"])} scan(s), '
          f'{summary["wall_s"]:.3f}s wall attributed\n')
    print(f'{"stage":<14}{"blame_s":>10}{"frac":>8}')
    for stage, s in sorted(totals.items(), key=lambda kv: -kv[1]):
        print(f'{stage:<14}{s:>10.4f}'
              f'{summary["blame_frac"][stage]:>8.2%}')
    print(f'\nbound_by: {summary["bound_by"]}')
    if summary['suggest']:
        knobs = ', '.join(f'{k} {v}'
                          for k, v in summary['suggest'].items())
        print(f'suggest:  {knobs}')
    if summary['note']:
        print(f'note:     {summary["note"]}')
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('trace', help='Chrome trace-event JSON file')
    ap.add_argument('--check', action='store_true',
                    help='validate the trace-event schema and exit')
    ap.add_argument('--json', action='store_true',
                    help='emit the blame summary as JSON')
    args = ap.parse_args(argv)
    try:
        with open(args.trace) as fh:
            trace = json.load(fh)
    except (OSError, ValueError) as e:
        print(f'cannot read trace {args.trace!r}: {e}', file=sys.stderr)
        return 2
    if args.check:
        return check(trace)
    return report(trace, args.json)


if __name__ == '__main__':
    sys.exit(main())
