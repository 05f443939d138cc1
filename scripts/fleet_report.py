#!/usr/bin/env python
"""Cross-host fleet federation report.

Merges per-process metric snapshots into one fleet view — the offline
twin of a live process's ``GET /debug/fleet``.  Two modes:

  scripts/fleet_report.py host1.jsonl host2.jsonl ...
      merge per-host JSONL snapshot files (written by
      ``kyverno_tpu.observability.fleet.write_snapshot`` — one line
      per snapshot; no program writes them since ``bench.py`` went,
      ``tests/test_fleet.py`` does) with
      the exact merge the live endpoint uses, so the CLI and a running
      process can never disagree on the math.

  scripts/fleet_report.py --url http://127.0.0.1:6060
      fetch the live fleet report from a --profile process.

``--json`` prints the machine-readable document instead of the table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def fetch_report(url: str) -> dict:
    from urllib.request import urlopen
    with urlopen(url.rstrip('/') + '/debug/fleet', timeout=10) as resp:
        return json.loads(resp.read().decode('utf-8'))


def merge_files(paths) -> dict:
    from kyverno_tpu.observability.fleet import (FleetRegistry,
                                                 read_snapshot_files)
    docs = read_snapshot_files(paths)
    if not docs:
        raise SystemExit('no snapshots found in: ' + ', '.join(paths))
    merged = FleetRegistry.merge(docs)
    return {
        'enabled': True,
        'processes': merged['identities'],
        'merged': merged,
        'skew': None,
    }


def print_table(report: dict) -> None:
    if not report.get('enabled', True):
        print('fleet observatory not configured (KTPU_FLEET=0 or no '
              '--profile registry)')
        return
    processes = report.get('processes') or []
    print(f'fleet: {len(processes)} process(es)')
    for ident in processes:
        print(f'  {ident.get("host", "?")} pid={ident.get("pid", "?")} '
              f'process_index={ident.get("process_index", "?")}')
    skew = report.get('skew')
    if skew:
        print(f'skew: {skew.get("mesh")} {float(skew.get("skew", 1)):.2f}x '
              f'slow_shard={skew.get("slow_shard")} '
              f'sustained={skew.get("sustained")}')
        if skew.get('note'):
            print(f'  {skew["note"]}')
    merged = report.get('merged') or {}
    print()
    print(f'{"merged counter":<52} {"total":>14}')
    for name, entries in (merged.get('counters') or {}).items():
        total = sum(v for _k, v in entries)
        print(f'{name:<52} {total:>14g}')
    print(f'{"merged gauge":<52} {"value":>14}')
    for name, entries in (merged.get('gauges') or {}).items():
        total = sum(v for _k, v in entries)
        print(f'{name:<52} {total:>14g}')
    hists = merged.get('hists') or {}
    if hists:
        print(f'{"merged histogram":<52} {"count":>8} {"sum":>12}')
        for name, h in hists.items():
            count = sum(e[1] for e in h.get('series') or [])
            total = sum(e[2] for e in h.get('series') or [])
            flag = '  [bucket_conflict]' if h.get('bucket_conflict') \
                else ''
            print(f'{name:<52} {count:>8d} {total:>12.6g}{flag}')


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog='fleet_report',
        description='cross-host fleet metric federation report')
    parser.add_argument('paths', nargs='*',
                        help='per-host JSONL snapshot files to merge '
                             'offline')
    parser.add_argument('--url', default='',
                        help='fetch /debug/fleet from a live --profile '
                             'process instead of merging files')
    parser.add_argument('--json', action='store_true', dest='as_json',
                        help='print the JSON document')
    args = parser.parse_args(argv)
    if args.url:
        report = fetch_report(args.url)
    elif args.paths:
        report = merge_files(args.paths)
    else:
        parser.print_usage(sys.stderr)
        return 2
    if args.as_json:
        json.dump(report, sys.stdout, indent=2, sort_keys=True)
        print()
    else:
        print_table(report)
    return 0


if __name__ == '__main__':
    sys.exit(main())
