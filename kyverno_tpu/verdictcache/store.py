"""Digest-keyed verdict cache: in-memory LRU front + on-disk snapshots
that grow by appended deltas.

One **generation** per (policy-set fingerprint × engine rev) holds
``spec digest → verdict row`` where a row is the fused report-path
output of one resource (``BatchScanner.scan_report_results``): the
result dicts (timestamps stripped — replay stamps the current tick),
the summary, and the indexes of the contributing policies (the
fingerprint pins policy-set order, so indexes are stable across
processes).  Rescans replay hit rows in O(1) instead of re-evaluating
the resource×rule matrix; only digests that changed ship to the device.

Result dicts are **shared between rows**.  The fused path hands
``store`` flyweights that one (rule response, policy, second) shares
across every resource (``reports/results.py`` ``_rule_result``);
``store`` keeps one timestamp-free copy per flyweight it is handed
(interned by the flyweight's identity until the next ``flush``), so
rows that received the same result refer to one object.  Stored
results are immutable, like the flyweights they copy.

Persistence: one snapshot file per generation
(``<fingerprint>-<rev>.vrows``): the magic ``KTVC3``, then a sequence of
frames, each its kind (``B`` base or ``D`` delta), its payload's length
and SHA-256, then the payload.  A payload is zlib-compressed JSON, a
table: ``{"t": [result, ...], "r": {digest: {"u", "r": [table index,
...], "s", "p"}}}``; a delta adds ``"x": [dropped digest, ...]``.  Each
distinct result is written once a frame (deduplicated by identity, then
by its serialised bytes); a reload shares one dict per table entry among
the rows of a frame that refer to it.

The first frame is a base: every resident row.  Each later flush appends
one delta: the rows stored and the digests dropped (invalidated or
evicted from the memory front) since the flush before it.  A flush
**compacts** instead, rewriting the file as one base through tmp-file +
``os.replace`` (the ``aotcache/store.py`` protocol), when there is no base
it can append to or when the log's entries since the base (a row stored
or a digest dropped, this flush's included) would exceed the base's row
count: the file and a reload stay under twice a base, and the rewrites
cost one base write per base-sized log.  A full scan therefore writes
what one snapshot writes, and a churn tick writes what it changed.

A reload replays the base, then each delta (its drops, then its rows).
A bad magic — ``KTVC2`` and ``KTVC1`` files among them — or a bad base
frame deletes the file and loads as empty: a bad snapshot costs a rescan,
never a crash or a stale verdict.  A delta frame that fails its length or
hash check ends the read: the frames before it are the state after an
earlier complete flush, and they load; the next flush compacts, so
nothing is appended after a torn tail.  Disk eviction is LRU by mtime
against a byte budget (a file's size includes its log); the memory front
is an entry-capped LRU.

Knobs:

* ``KTPU_VERDICT_CACHE`` — ``0``/``off`` disables the cache entirely
  (default on); the dense full scan is always the correctness oracle.
* ``KTPU_VERDICT_CACHE_DIR`` — snapshot directory (default
  ``<repo>/.cache/verdicts``; empty string keeps the cache
  memory-only).
* ``KTPU_VERDICT_CACHE_MAX`` — on-disk byte budget, default 256 MiB.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import struct
import tempfile
import threading
import time
import zlib
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .. import faults
from ..observability import device as devtel
from .keys import engine_rev, generation_key

_log = logging.getLogger('kyverno.verdictcache')

#: snapshot file: the magic, then frames (KTVC3: a base and appended
#: deltas; a KTVC2 or KTVC1 snapshot fails the magic check and loads as
#: empty)
_MAGIC = b'KTVC3\n'
#: a frame's header: its kind, its payload's length and SHA-256
_FRAME = struct.Struct('>cQ32s')
_BASE = b'B'
_DELTA = b'D'
_SUFFIX = '.vrows'

VERDICT_CACHE_HITS = 'kyverno_tpu_verdict_cache_hits_total'
VERDICT_CACHE_MISSES = 'kyverno_tpu_verdict_cache_misses_total'
VERDICT_CACHE_EVICTIONS = 'kyverno_tpu_verdict_cache_evictions_total'
VERDICT_SNAPSHOT_RESULTS = 'kyverno_tpu_verdict_snapshot_results_total'
RESCAN_ROWS_SCANNED = 'kyverno_tpu_rescan_rows_scanned'
RESCAN_ROWS_REPLAYED = 'kyverno_tpu_rescan_rows_replayed'

_DEFAULT_MAX_BYTES = 256 << 20
#: memory-front entry cap (rows are a few hundred bytes; 2M entries is
#: the 1M-Pod steady state with headroom, bounded without a knob)
_MEM_MAX_ENTRIES = 2_000_000
#: flyweights interned between two flushes before the table starts
#: over, which bounds what it pins (a reconcile hands a few hundred
#: shared ones, plus one per PSS FAIL cell that no other row shares)
_INTERN_MAX = 1 << 16

_dumps = json.JSONEncoder(separators=(',', ':')).encode
_dump_str = json.encoder.encode_basestring_ascii


def _frame(kind: bytes, payload: bytes) -> bytes:
    return _FRAME.pack(kind, len(payload),
                       hashlib.sha256(payload).digest()) + payload


def _read_frame(raw: bytes, pos: int
                ) -> Optional[Tuple[bytes, Dict[str, dict], list, int]]:
    """The frame at ``pos``: its kind, its rows (results resolved from
    its table), the digests it drops and where it ends; None where it is
    cut short, fails its hash or does not decode."""
    start = pos + _FRAME.size
    if start > len(raw):
        return None
    kind, length, digest = _FRAME.unpack_from(raw, pos)
    payload = raw[start:start + length]
    if len(payload) != length or hashlib.sha256(payload).digest() != digest:
        return None
    try:
        doc = json.loads(zlib.decompress(payload).decode())
        table = doc['t']
        rows = doc['r']
        for row in rows.values():
            row['r'] = [table[i] for i in row['r']]
        dropped = doc.get('x', [])
    except Exception:  # noqa: BLE001 - a frame that does not decode
        return None
    return kind, rows, dropped, start + length


def _stat_id(path: str) -> Optional[Tuple[int, int]]:
    """``(inode, size)`` of the file at ``path``; None where there is
    none."""
    try:
        st = os.stat(path)
    except OSError:
        return None
    return st.st_ino, st.st_size


def replay_row(row: dict, policies, ts: int
               ) -> Tuple[List[dict], dict, list]:
    """Row → the ``(results, summary, row_policies)`` triple
    ``scan_report_results`` would yield, stamped with ``ts`` (all
    results of one fused row share the tick's timestamp, so sort order
    is unaffected).

    Re-stamping is lazy: the stamped form is kept on the row (``'rt'``)
    with the tick second it carries (``'t'``), so replays within the
    same second (fast reconcile loops over a large cache) return the
    shared dicts with zero per-result copies.  Stamped results are
    immutable from then on — a later tick with a different second
    builds fresh copies, never mutating what an earlier report may
    still reference.  ``'r'`` keeps the timestamp-free results that
    ``flush`` persists; neither ``'t'`` nor ``'rt'`` is persisted."""
    if row.get('t') == ts:
        results = row['rt']
    else:
        stamp = {'seconds': ts}
        results = [dict(r, timestamp=stamp) for r in row['r']]
        row['rt'] = results
        row['t'] = ts
    return (results, dict(row['s']),
            [policies[p] for p in row['p'] if p < len(policies)])


def _reg():
    from ..observability.metrics import global_registry
    return global_registry()


def publish_tick(scanned: int, replayed: int) -> None:
    """Per-tick rescan gauges: how many rows the last reconcile shipped
    to the device vs replayed from the cache (no-op unconfigured)."""
    reg = _reg()
    if reg is None:
        return
    reg.set_gauge(RESCAN_ROWS_SCANNED, float(scanned))
    reg.set_gauge(RESCAN_ROWS_REPLAYED, float(replayed))


def _env_enabled() -> bool:
    return os.environ.get('KTPU_VERDICT_CACHE', '1') not in ('0', 'off')


def _env_root() -> Optional[str]:
    root = os.environ.get(
        'KTPU_VERDICT_CACHE_DIR',
        os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), '.cache', 'verdicts'))
    return root or None


def _env_max_bytes() -> int:
    try:
        return int(os.environ.get('KTPU_VERDICT_CACHE_MAX',
                                  str(_DEFAULT_MAX_BYTES)))
    except ValueError:
        return _DEFAULT_MAX_BYTES


class VerdictCache:
    """One generation of digest-keyed verdict rows.

    Row schema in memory: ``{'u': uid, 'r': [result dicts, no
    timestamp key], 's': summary, 'p': [policy indexes]}``, plus the
    replay's ``'t'`` / ``'rt'`` (:func:`replay_row`).  The result dicts
    are shared: every row handed the same flyweight, or loaded from the
    same snapshot table entry, refers to one immutable dict.  In the
    snapshot a row is ``{'u', 'r': [table indexes], 's', 'p'}`` beside
    the table ``'t'`` of distinct results (see the module docstring).
    """

    def __init__(self, fingerprint: str, root: Optional[str] = None,
                 max_bytes: Optional[int] = None,
                 max_entries: int = _MEM_MAX_ENTRIES,
                 rev: Optional[str] = None):
        self.fingerprint = fingerprint
        self.rev = rev or engine_rev()
        self.max_bytes = _env_max_bytes() if max_bytes is None else max_bytes
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._rows: 'OrderedDict[str, dict]' = OrderedDict()
        self._by_uid: Dict[str, Set[str]] = {}
        # id(flyweight) -> its timestamp-free copy; the flyweights are
        # pinned, so no id of theirs is reused while it keys the map
        self._interned: Dict[int, dict] = {}
        self._pinned: List[dict] = []
        # what changed since the last flush, disjoint: digests stored
        # (in the order of their last store) and digests dropped
        self._stored: Dict[str, None] = {}
        self._dropped: Set[str] = set()
        # the snapshot file as this cache last wrote or read it: the rows
        # of its base (None: no base to append to), the entries of its
        # deltas since, and its (inode, size); guarded by _write_lock
        self._write_lock = threading.Lock()
        self._base_rows: Optional[int] = None
        self._log_entries = 0
        self._file_id: Optional[Tuple[int, int]] = None
        #: what the last flush wrote: (result references, table entries)
        self.last_flush = (0, 0)
        # local lookup outcome counters: benchmarks and the decision-
        # provenance cross-checks read them without a metrics registry
        self._hits = 0
        self._misses = 0
        if root is not None:
            try:
                os.makedirs(root, exist_ok=True)
            except OSError:
                root = None
        self.root = root
        self._load()

    @classmethod
    def from_env(cls, fingerprint: str) -> Optional['VerdictCache']:
        """The env-configured cache, or None when KTPU_VERDICT_CACHE is
        off (callers then run every row through the dense scan)."""
        if not _env_enabled():
            return None
        root = _env_root()
        if root is not None:
            try:
                os.makedirs(root, exist_ok=True)
            except OSError:
                root = None
        return cls(fingerprint, root=root)

    def path(self) -> Optional[str]:
        if self.root is None:
            return None
        return os.path.join(
            self.root, generation_key(self.fingerprint, self.rev) + _SUFFIX)

    def __len__(self) -> int:
        with self._lock:
            return len(self._rows)

    # -- lookups -----------------------------------------------------------

    def lookup(self, digest: str) -> Optional[dict]:
        """The cached row for one spec digest, or None (miss).  Hits
        refresh the memory-LRU position; both outcomes count."""
        with self._lock:
            row = self._rows.get(digest)
            if row is not None:
                self._rows.move_to_end(digest)
                self._hits += 1
            else:
                self._misses += 1
        reg = _reg()
        if reg is not None:
            if row is None:
                reg.inc(VERDICT_CACHE_MISSES)
            else:
                reg.inc(VERDICT_CACHE_HITS)
        return row

    def peek(self, digest: str) -> Optional[dict]:
        """``lookup`` without outcome accounting: composite caches
        (``verdictcache/partitioned.py``) probe every member generation
        per digest but count ONE hit or miss for the whole lookup —
        per-member counting would inflate the metrics by the partition
        count.  Refreshes the LRU position like a real hit."""
        with self._lock:
            row = self._rows.get(digest)
            if row is not None:
                self._rows.move_to_end(digest)
            return row

    # -- writes ------------------------------------------------------------

    def store(self, digest: str, uid: str, results: List[dict],
              summary: dict, policy_indexes: List[int]) -> None:
        """Record one scanned row.  ``results`` are the shared fused-path
        flyweight dicts — never mutated; the row refers to one
        timestamp-free copy per flyweight (the first row handed it
        makes the copy, every later one shares it), so replay can stamp
        the replaying tick and ``flush`` finds the sharing by identity."""
        with self._lock:
            interned = self._interned
            if len(interned) > _INTERN_MAX:
                interned.clear()
                self._pinned.clear()
            kept = []
            for r in results:
                copy = interned.get(id(r))
                if copy is None:
                    copy = interned[id(r)] = {
                        k: v for k, v in r.items() if k != 'timestamp'}
                    self._pinned.append(r)
                kept.append(copy)
            row = {'u': uid, 'r': kept, 's': dict(summary),
                   'p': list(policy_indexes)}
            old = self._rows.get(digest)
            if old is not None:
                self._unindex(digest, old)
            self._rows[digest] = row
            self._rows.move_to_end(digest)
            self._by_uid.setdefault(uid, set()).add(digest)
            self._stored.pop(digest, None)
            self._stored[digest] = None
            self._dropped.discard(digest)
            evicted = self._evict_memory()
        reg = _reg()
        if evicted and reg is not None:
            reg.inc(VERDICT_CACHE_EVICTIONS, float(evicted))

    def invalidate_uid(self, uid: str) -> int:
        """Drop every entry recorded for ``uid`` (resource changed or
        deleted — a recreated resource with a stale uid must never
        replay old verdicts).  Returns the number dropped."""
        with self._lock:
            digests = self._by_uid.pop(uid, None)
            if not digests:
                return 0
            dropped = 0
            for d in digests:
                if self._rows.pop(d, None) is not None:
                    self._drop(d)
                    dropped += 1
        return dropped

    def _drop(self, digest: str) -> None:
        """Log a digest that left the memory front.  Caller holds the
        lock."""
        self._stored.pop(digest, None)
        self._dropped.add(digest)

    def _evict_memory(self) -> int:
        """Drop the least recently used rows over the entry cap; returns
        how many.  Caller holds the lock."""
        evicted = 0
        while len(self._rows) > self.max_entries:
            d, row = self._rows.popitem(last=False)
            self._unindex(d, row)
            self._drop(d)
            evicted += 1
        return evicted

    def _unindex(self, digest: str, row: dict) -> None:
        digests = self._by_uid.get(row.get('u', ''))
        if digests is not None:
            digests.discard(digest)
            if not digests:
                self._by_uid.pop(row.get('u', ''), None)

    # -- replay ------------------------------------------------------------

    def replay(self, row: dict, policies, ts: int
               ) -> Tuple[List[dict], dict, list]:
        """:func:`replay_row`."""
        return replay_row(row, policies, ts)

    # -- persistence -------------------------------------------------------

    def _load(self) -> None:
        """Populate the memory front from this generation's snapshot:
        the base frame, then each delta frame in order (its drops, then
        its rows).  A short or unframed file, or a base frame that fails
        its length, hash or decoding, is deleted and loaded as empty —
        never raised.  A delta frame that fails them ends the read: the
        frames before it load, and the next flush compacts."""
        path = self.path()
        if path is None or not os.path.exists(path):
            return
        try:
            # an injected verdict_snapshot_read fault degrades exactly
            # like an unreadable file: load as empty, rescan refills
            faults.check(faults.SITE_VERDICT_SNAPSHOT)
            with open(path, 'rb') as f:
                inode = os.fstat(f.fileno()).st_ino
                raw = f.read()
        except Exception:  # noqa: BLE001 - unreadable snapshot: empty
            return
        rows: Optional[Dict[str, dict]] = None
        base_rows = entries = 0
        pos = len(_MAGIC) if raw.startswith(_MAGIC) else len(raw)
        while pos < len(raw):
            frame = _read_frame(raw, pos)
            if frame is None:
                break
            kind, got, dropped, pos_next = frame
            if rows is None:
                if kind != _BASE:
                    break
                rows, base_rows = got, len(got)
            else:
                if kind != _DELTA:
                    break
                for d in dropped:
                    rows.pop(d, None)
                for d, row in got.items():
                    rows.pop(d, None)
                    rows[d] = row
                entries += len(got) + len(dropped)
            pos = pos_next
        if rows is None:
            _log.warning('verdict snapshot %s corrupt; dropping',
                         os.path.basename(path))
            self._drop_file(path)
            return
        with self._lock:
            for digest, row in rows.items():
                self._rows[digest] = row
                self._by_uid.setdefault(row.get('u', ''), set()).add(digest)
            self._evict_memory()
        if pos < len(raw):
            _log.warning('verdict snapshot %s: torn tail after %d bytes; '
                         'the next flush compacts', os.path.basename(path),
                         pos)
        else:
            self._base_rows, self._log_entries = base_rows, entries
            self._file_id = (inode, len(raw))
        try:
            os.utime(path)  # disk LRU works off mtime, like the AOT store
        except OSError:
            pass

    @staticmethod
    def _drop_file(path: str) -> None:
        try:
            os.unlink(path)
        except OSError:
            pass

    def flush(self) -> bool:
        """Persist what changed since the last flush, then evict older
        generation snapshots LRU-by-mtime to fit the byte budget.

        Appends one delta frame (the rows stored and the digests dropped
        since the last flush), or **compacts**: rewrites the generation
        as one base frame of every resident row, tmp + rename, where
        there is no base to append to (no file yet, a torn tail, a failed
        write), where the file is not as this cache left it, or where
        the log's entries since the base, this flush's included, would
        exceed the base's rows.  Records the stage ``flush_delta`` or
        ``flush_compact``.  Returns True when a frame was written."""
        path = self.path()
        t0 = time.monotonic()
        with self._write_lock:
            with self._lock:
                # the next reconcile hands flyweights of its own; what
                # it shares with these rows is found again by bytes below
                self._interned.clear()
                self._pinned.clear()
                self.last_flush = (0, 0)
                stored, dropped = self._stored, self._dropped
                if path is None or not (stored or dropped):
                    stored.clear()
                    dropped.clear()
                    return False
                self._stored, self._dropped = {}, set()
                entries = len(stored) + len(dropped)
                compact = (self._base_rows is None or
                           _stat_id(path) != self._file_id or
                           self._log_entries + entries > self._base_rows)
                if compact:
                    base_rows = len(self._rows)
                    text, refs, distinct = self._snapshot_json(
                        self._rows.items())
                else:
                    text, refs, distinct = self._snapshot_json(
                        [(d, self._rows[d]) for d in stored], dropped)
                payload = zlib.compress(text.encode(), 3)
            reg = _reg()
            if reg is not None:
                reg.inc(VERDICT_SNAPSHOT_RESULTS, float(refs), entry='ref')
                reg.inc(VERDICT_SNAPSHOT_RESULTS, float(distinct),
                        entry='table')
            self.last_flush = (refs, distinct)
            try:
                if compact:
                    self._replace(path, _MAGIC + _frame(_BASE, payload))
                    self._base_rows, self._log_entries = base_rows, 0
                else:
                    with open(path, 'ab') as f:
                        f.write(_frame(_DELTA, payload))
                    self._log_entries += entries
                self._file_id = _stat_id(path)
            except OSError:
                self._base_rows = None
                return False
        self._evict_disk(keep=path)
        if compact:
            devtel.record_stage('flush_compact', time.monotonic() - t0)
        else:
            devtel.record_stage('flush_delta', time.monotonic() - t0)
        return True

    def _replace(self, path: str, data: bytes) -> None:
        """Write ``path`` whole through a tmp file and ``os.replace``, so
        a reader never sees it half written."""
        fd, tmp = tempfile.mkstemp(dir=self.root, suffix='.tmp')
        try:
            with os.fdopen(fd, 'wb') as f:
                f.write(data)
            os.replace(tmp, path)
        except BaseException:
            self._drop_file(tmp)
            raise

    def _snapshot_json(self, items: Iterable[Tuple[str, dict]],
                       dropped: Optional[Set[str]] = None
                       ) -> Tuple[str, int, int]:
        """A frame's JSON text over ``items`` (digest, row), the result
        references those rows make and the table entries they refer to;
        with ``dropped``, a delta's: the digests it drops under ``"x"``.
        Each row is written straight to text, so the snapshot keeps no
        container a row alive (thousands of them would wake the cyclic
        collector over the scan's whole heap).  Caller holds the lock."""
        table: List[str] = []
        by_id: Dict[int, str] = {}     # id(result) -> its index, as text
        by_text: Dict[str, str] = {}   # a result's JSON -> its index
        policies: Dict[tuple, str] = {}  # a row's policy indexes, as text
        rows = []
        refs = 0
        for digest, row in items:
            results = row['r']
            refs += len(results)
            idx = []
            for r in results:
                i = by_id.get(id(r))
                if i is None:
                    text = _dumps(r)
                    i = by_text.get(text)
                    if i is None:
                        i = by_text[text] = str(len(table))
                        table.append(text)
                    by_id[id(r)] = i
                idx.append(i)
            key = tuple(row['p'])
            p = policies.get(key)
            if p is None:
                p = policies[key] = ','.join(map(str, key))
            rows.append('%s:{"u":%s,"r":[%s],"s":%s,"p":[%s]}' % (
                _dump_str(digest), _dump_str(row['u']), ','.join(idx),
                _dumps(row['s']), p))
        text = '{"t":[%s],"r":{%s}' % (','.join(table), ','.join(rows))
        if dropped is not None:
            text += ',"x":[%s]' % ','.join(map(_dump_str, dropped))
        return text + '}', refs, len(table)

    def _evict_disk(self, keep: str) -> None:
        """Drop oldest generation snapshots until the directory fits the
        budget (the just-written snapshot always survives)."""
        entries = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return
        for name in names:
            p = os.path.join(self.root, name)
            if name.endswith('.tmp'):
                try:  # orphaned partial writes from killed processes
                    if time.time() - os.stat(p).st_mtime > 600:
                        os.unlink(p)
                except OSError:
                    pass
                continue
            if not name.endswith(_SUFFIX):
                continue
            try:
                st = os.stat(p)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, p))
        entries.sort()
        total = sum(sz for _, sz, _ in entries)
        evicted = 0
        for _, sz, p in entries:
            if total <= self.max_bytes or p == keep:
                continue
            try:
                os.unlink(p)
                total -= sz
                evicted += 1
            except OSError:
                pass
        reg = _reg()
        if evicted and reg is not None:
            reg.inc(VERDICT_CACHE_EVICTIONS, float(evicted))

    def stats(self) -> Dict[str, int]:
        path = self.path()
        size = 0
        if path is not None:
            try:
                size = os.stat(path).st_size
            except OSError:
                size = 0
        with self._lock:
            return {'entries': len(self._rows), 'snapshot_bytes': size,
                    'hits': self._hits, 'misses': self._misses}
