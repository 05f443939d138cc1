#!/usr/bin/env python
"""ktpu-lint driver: run every static-analysis pass over the tree.

    python scripts/analyze.py                  # table of findings
    python scripts/analyze.py --json           # machine-readable
    python scripts/analyze.py --strict         # nonzero on any
                                               # non-baseline finding
                                               # or stale baseline entry
    python scripts/analyze.py --write-baseline # regenerate the
                                               # grandfather file
    python scripts/analyze.py --knob-table     # README KTPU_* table
    python scripts/analyze.py --list-rules     # rule id reference

Default file set: ``kyverno_tpu/`` and ``scripts/``.
The committed baseline lives at ``.ktpu-baseline.json``; every entry
must carry a ``reason`` (``--strict`` refuses unjustified entries).
Per-line suppressions: ``# ktpu: noqa[KTPU101] -- reason``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from kyverno_tpu.analysis import (Analyzer, RULES, load_baseline,  # noqa: E402
                                  write_baseline)
from kyverno_tpu.analysis.core import (DEFAULT_BASELINE,  # noqa: E402
                                       DEFAULT_SOURCE_PATHS)
from kyverno_tpu.analysis.knobs import render_knob_table  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('paths', nargs='*', default=None,
                    help='files/dirs to analyze (default: '
                         'kyverno_tpu scripts)')
    ap.add_argument('--json', action='store_true', dest='as_json')
    ap.add_argument('--strict', action='store_true',
                    help='exit nonzero on non-baseline findings, '
                         'stale baseline entries, or unjustified '
                         'baseline entries')
    ap.add_argument('--baseline', default=None,
                    help=f'baseline path (default: {DEFAULT_BASELINE})')
    ap.add_argument('--no-baseline', action='store_true',
                    help='ignore the committed baseline')
    ap.add_argument('--write-baseline', action='store_true',
                    help='grandfather every current finding into the '
                         'baseline file (then justify each entry)')
    ap.add_argument('--rules', default=None,
                    help='comma-separated rule ids to run')
    ap.add_argument('--knob-table', action='store_true',
                    help='print the generated KTPU_* README table')
    ap.add_argument('--span-table', action='store_true',
                    help='print the generated README span table')
    ap.add_argument('--debug-table', action='store_true',
                    help='print the generated README debug-endpoint '
                         'table (profiling-server route registry)')
    ap.add_argument('--list-rules', action='store_true')
    ap.add_argument('--graph-dump', default=None, metavar='FN',
                    help='debug: print the resolved callees and taint '
                         'facts for one function (bare name, '
                         'Class.method, or module:qualname); '
                         'honors --json')
    args = ap.parse_args(argv)

    if args.knob_table:
        print(render_knob_table())
        return 0
    if args.span_table:
        from kyverno_tpu.analysis.catalog_pass import render_span_table
        print(render_span_table())
        return 0
    if args.debug_table:
        from kyverno_tpu.observability.profiling import render_debug_table
        print(render_debug_table())
        return 0
    if args.list_rules:
        for rid in sorted(RULES):
            print(f'{rid}  {RULES[rid].summary}')
        return 0

    paths = args.paths or [p for p in DEFAULT_SOURCE_PATHS
                           if os.path.exists(os.path.join(REPO_ROOT, p))]
    baseline = None if args.no_baseline else \
        (args.baseline or os.path.join(REPO_ROOT, DEFAULT_BASELINE))
    rules = [r.strip() for r in args.rules.split(',')] \
        if args.rules else None
    analyzer = Analyzer(paths, REPO_ROOT, baseline_path=baseline,
                        rules=rules)

    if args.graph_dump:
        from kyverno_tpu.analysis.jitgraph import jit_graph
        graph = jit_graph(analyzer.ctx)
        matches = graph.function_by_name(args.graph_dump)
        if not matches:
            print(f'no function matches {args.graph_dump!r}',
                  file=sys.stderr)
            return 2
        dumps = [graph.graph_dump(mi, fn) for mi, fn in matches]
        if args.as_json:
            print(json.dumps(dumps, indent=2))
        else:
            for d in dumps:
                print(f'{d["qualname"]}  ({d["file"]}:{d["line"]})')
                print(f'  jit-reachable: {d["jit_reachable"]}')
                if d.get('class'):
                    print(f'  class: {d["class"]}')
                print('  callees:')
                if not d['callees']:
                    print('    (none resolved)')
                for c in d['callees']:
                    reach = ' [jit-reachable]' if c['jit_reachable'] \
                        else ''
                    print(f'    {c["qualname"]}  ({c["file"]}:'
                          f'{c["line"]}, called at line '
                          f'{c["call_line"]}){reach}')
                taint = d.get('taint') or {}
                if taint.get('params'):
                    print(f'  tainted params (depth '
                          f'{taint.get("depth")}): '
                          f'{", ".join(taint["params"])}')
                    if taint.get('chain'):
                        print(f'  taint chain: '
                              f'{" -> ".join(taint["chain"])}')
                    print(f'  tainted locals: '
                          f'{", ".join(taint.get("names", [])) or "-"}')
                else:
                    print('  tainted params: (none)')
        return 0

    report = analyzer.run()

    if args.write_baseline:
        target = baseline or os.path.join(REPO_ROOT, DEFAULT_BASELINE)
        # regenerate from every kept finding — new AND already
        # grandfathered — so a rewrite never drops still-matching
        # entries, and carry existing justifications over by key
        prior = {(e.get('rule'), e.get('path'), e.get('match')):
                 str(e.get('reason', ''))
                 for e in load_baseline(target)}
        everything = report.active + report.baselined
        write_baseline(target, everything)
        with open(target, encoding='utf-8') as fh:
            doc = json.load(fh)
        for e in doc['entries']:
            r = prior.get((e['rule'], e['path'], e['match']), '')
            if r and not r.startswith('TODO'):
                e['reason'] = r
        with open(target, 'w', encoding='utf-8') as fh:
            json.dump(doc, fh, indent=2)
            fh.write('\n')
        print(f'wrote {len(doc["entries"])} entries to {target} — '
              f'justify each "reason" before committing')
        return 0

    if args.as_json:
        print(json.dumps(report.to_json(), indent=2))
    else:
        for f in report.active:
            print(f.render())
        for e in report.stale_baseline:
            print(f'stale baseline entry: {e.get("rule")} '
                  f'{e.get("path")} ({e.get("match")!r}) no longer '
                  f'matches — remove it')
        for e in report.errors:
            print(e, file=sys.stderr)
        n_files = len(analyzer.files)
        print(f'{len(report.active)} finding(s), '
              f'{len(report.baselined)} baselined, '
              f'{len(report.suppressed)} suppressed, '
              f'{len(report.stale_baseline)} stale baseline '
              f'entr(y/ies) over {n_files} files / '
              f'{len(RULES)} rules')

    if report.active or report.errors:
        return 1
    if args.strict and report.stale_baseline:
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
