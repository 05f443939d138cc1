"""Driver of the admission webhook in a multi-tenant cluster: the loops, the
sweep and the answer check of ``drivers/webhook.py``, with a set-up of its
own and the configuration's further guarantees.

Installed are the committed pack as Enforce ``ClusterPolicy`` objects (not
replicated) and, from the configuration's ``tenants`` generator, a few
namespaced Enforce ``Policy`` objects in each tenant namespace.  The program
is expected to serve them all from ONE compiled validate set
(``policycache.Cache.get_installed``); a program that has no such set would
compile a scanner for every namespace a request comes from, so the run says
so and ends.  Set-up waits for that one set at most ``ready_within_s``.

Beside the base driver's check (a seeded sample of answers against the same
chain with ``device=False``), allowed/denied of EVERY answered request is
held to the plain reference: ``benchmarks/reference/tenants.py`` for the
tenants' policies, and the ``device=False`` chain over the cluster policies
alone for theirs; and the message of EVERY denied answer is held to the
reference's list of failing tenant policies: it names exactly those, each in
the words of its own policy, and speaks of no other tenant.

The driver also hands the readers the stage histogram over the window
(``counters['stages']``, as the reports driver does), for the request
thread's ``candidates`` stage, which is in no field of ``stats()``.
"""

from __future__ import annotations

import bisect
import copy
import json
import re
import statistics
import time

import benchlib
from benchlib import say

_webhook = benchlib.load_module('drivers', 'webhook')
_reference = benchlib.load_module('reference', 'tenants')


_TENANT = re.compile(r'tenant-\d+')
_RULE = re.compile(r'^  ([^\s:][^:\n]*):(?: |$)', re.M)


def message_problem(message: str, namespace: str, failing: list,
                    texts: dict):
    """What is wrong with a denial message, or None.  ``failing`` is the
    reference's ``namespace/name`` list and ``texts`` gives, by
    ``namespace/name``, a tenant policy's rule name and message.  The
    tenants' rules among those the message names (the keys two blanks in,
    under the policies' names at column 0; a cluster policy may share a
    tenant policy's name, never its rule's) are those of ``failing``; each
    is there in its own policy's words (the emitter folds lines, so blanks
    are not compared); no other tenant is spoken of."""
    rules = {texts[f][0] for f in texts}
    named = sorted(set(_RULE.findall(message)) & rules)
    if named != sorted(texts[f][0] for f in failing) or any(
            f.split('/', 1)[0] != namespace for f in failing):
        return f'names the tenant rules {named}'
    flat = ' '.join(message.split())
    for f in failing:
        if ' '.join(texts[f][1].split()) not in flat:
            return f'does not word {f} as its policy does'
    others = set(_TENANT.findall(message)) - {namespace}
    if others:
        return f'speaks of {sorted(others)}'
    return None


class Driver(_webhook.Driver):

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from kyverno_tpu.api.policy import Policy
        from kyverno_tpu.policycache import cache as pcache
        from kyverno_tpu.serving import breaker
        from kyverno_tpu.webhooks.handlers import ResourceHandlers
        from kyverno_tpu.webhooks.server import WebhookServer
        t0 = time.monotonic()
        self.cluster_policies = []
        for policy in benchlib.load_policies(self.config['packs']):
            doc = copy.deepcopy(policy.raw)
            doc.setdefault('spec', {})['validationFailureAction'] = 'Enforce'
            self.cluster_policies.append(Policy(doc))
        spec = self.config['tenants']
        self.tenant_docs = benchlib.load_module(
            'generators', spec['generator']).generate(self.seed,
                                                      **spec['params'])
        policies = self.cluster_policies + [Policy(copy.deepcopy(d))
                                            for d in self.tenant_docs]
        spec = self.config['cluster']
        cluster = benchlib.load_module(
            'generators', spec['generator']).generate(self.seed,
                                                      **spec['params'])
        spec = self.config['requests']
        self.bodies = benchlib.load_module(
            'generators', spec['generator']).generate(
                self.seed, cluster, spec['pool'], **spec['params'])
        say(f'set-up/generate: {len(self.cluster_policies)} cluster and '
            f'{len(self.tenant_docs)} tenant enforce policies, '
            f'{len(self.bodies)} request bodies from a cluster of '
            f'{len(cluster)} in {time.monotonic() - t0:.1f}s')

        t0 = time.monotonic()
        self.failures = benchlib.FailureLog()
        self.cache = pcache.Cache()
        self.cache.warm_up(policies)
        installed_of = getattr(self.cache, 'get_installed', None)
        if installed_of is None:
            raise RuntimeError(
                'this program has no compiled set for namespaced policies '
                '(policycache.Cache.get_installed): it would compile one '
                'scanner for each namespace a request comes from')
        installed = installed_of(pcache.VALIDATE_ENFORCE, 'Pod')
        if len(installed) != len(policies):
            raise RuntimeError(f'{len(installed)} enforce policies are '
                               f'installed for Pods, not {len(policies)}')
        self.handlers = ResourceHandlers(self.cache, serving_mode='batch')
        self.server = WebhookServer(self.handlers)
        limit = float(self.config['guarantees']['ready_within_s'])
        if not self.handlers.wait_device_ready(installed, timeout=limit):
            raise RuntimeError(
                f'the compiled set did not come up in {limit:g}s: ' +
                '; '.join(
                    f'{b["state"]} after {b["failures"]} failures, last: '
                    f'{b.get("last_error", "")}'
                    for b in breaker.debug_report()['breakers']))
        self.scanner = self.handlers._device_scanner(installed)
        self.host_rules = len(self.scanner.cps.host_rules)
        say(f'set-up/build: {len(installed)} enforce policies '
            f'({len(self.scanner.cps.programs)} rule programs, '
            f'{self.host_rules} host rules) ready on the device in '
            f'{time.monotonic() - t0:.1f}s')

        t0 = time.monotonic()
        self.next_index = self.traffic['warm_requests']
        for body in self.bodies[:self.next_index]:
            self.server.handle('/validate/fail', body)
        self.handlers._get_batcher().reset_stats()
        self._stages_before = self._stages()
        say(f'set-up/warm: {self.next_index} requests in '
            f'{time.monotonic() - t0:.1f}s')

    def _stages(self) -> dict:
        from kyverno_tpu.observability import device as devtel
        return {dict(key).get('stage', ''): {'total_s': total,
                                             'count': count}
                for key, count, total in self.registry.histogram_series(
                    devtel.SCAN_STAGE_DURATION)}

    def counters(self) -> dict:
        return dict(super().counters(), stages=self._stage_counts)

    # -- the sweep (the builder's tool, not a measurement) --------------------

    def sweep(self, rates: list, step_seconds: float) -> None:
        """The base driver's sweep and its row, flag included, with what a
        short request needs to tell a queue that grows from one that stands:
        the count in flight read every 0.1 s (not at three moments), its
        fitted growth over the last two thirds of the step beside
        ``rate x p50`` (what stands in flight when nothing queues), and the
        median latency of each third."""
        batcher = self.handlers._get_batcher()
        first = self.next_index
        for rate in rates:
            batcher.reset_stats()
            self.failed = 0
            record = self._open(rate, step_seconds, first)
            self._reduce_open(record)
            start = self._window[0]
            first += len(self._due)
            lat, late = self._samples['latency_ms'], self._samples['late_ms']
            due = sorted(self._due.values())
            done = sorted(record[k][2] for k in self._due if k in record)
            ticks = [start + 0.1 * i
                     for i in range(1, int(step_seconds * 10) + 1)]
            flight = [bisect.bisect_right(due, t) - bisect.bisect_right(done, t)
                      for t in ticks]
            third = len(ticks) // 3
            thirds = [flight[:third], flight[third:2 * third],
                      flight[2 * third:]]
            tail_t, tail_n = ticks[third:], flight[third:]
            mt, mn = statistics.fmean(tail_t), statistics.fmean(tail_n)
            slope = sum((t - mt) * (n - mn) for t, n in zip(tail_t, tail_n)) \
                / sum((t - mt) ** 2 for t in tail_t)
            by_third = [[], [], []]
            for k, at in self._due.items():
                got = record.get(k)
                by_third[min(2, int(3 * (at - start) / step_seconds))].append(
                    (got[2] - at) * 1000.0 if got
                    else self.timeout_s * 1000.0)
            p50 = benchlib.quantile(lat, 0.5)
            stats = batcher.stats()
            at_thirds = [flight[third - 1], flight[2 * third - 1], flight[-1]]
            standing = rate * p50 / 1000.0
            growth = slope * (tail_t[-1] - tail_t[0])
            say('sweep: ' + json.dumps({
                'rate_per_s': rate, 'offered': len(lat), 'p50_ms': p50,
                'p95_ms': benchlib.quantile(lat, 0.95), 'max_ms': max(lat),
                'late_p95_ms': benchlib.quantile(late, 0.95) if late else None,
                'in_flight_at_1/3_2/3_end': at_thirds,
                'shed': stats['shed_total'], 'timeouts': self.failed,
                'occupancy_mean': stats['occupancy_mean'],
                'dispatches': stats['dispatches'],
                'sustained': not stats['shed_total'] and not self.failed
                and at_thirds[2] <= max(at_thirds[0], 2),
                'in_flight_mean_by_third': [statistics.fmean(t)
                                            for t in thirds],
                'in_flight_max': max(flight),
                'p50_ms_by_third': [benchlib.quantile(t, 0.5) if t else None
                                    for t in by_third],
                'standing_rate_x_p50': standing,
                'growth_over_last_two_thirds': growth,
                'growth_over_standing': growth / standing if standing
                else None}))
            time.sleep(min(5.0, step_seconds / 3))

    # -- the check ------------------------------------------------------------

    def check(self) -> list:
        self._stage_counts = benchlib.delta(self._stages_before,
                                            self._stages())
        problems = super().check()
        problems += self._against_the_reference()
        stats = self._stats
        offered = self.attempted
        on_device = stats.get('device_path_requests')
        if on_device != offered or stats.get('host_loop_requests') or \
                stats['shed_total']:
            problems.append(
                f'{on_device} of {offered} requests were answered by the '
                f'compiled path (host loop: {stats.get("host_loop")}, shed: '
                f'{stats["shed_total"]})')
        if stats.get('scanner_builds') != 1:
            problems.append(f'{stats.get("scanner_builds")} validate '
                            f'scanners were built, not 1')
        if self.host_rules:
            problems.append(f'{self.host_rules} rules stayed on the host')
        return problems

    def _against_the_reference(self) -> list:
        """allowed/denied of every answered request against the plain
        reference, and both answers in both kinds of namespace."""
        from kyverno_tpu.policycache import cache as pcache
        from kyverno_tpu.webhooks.handlers import ResourceHandlers
        from kyverno_tpu.webhooks.server import WebhookServer
        t0 = time.monotonic()
        cluster_cache = pcache.Cache()
        cluster_cache.warm_up(self.cluster_policies)
        host = WebhookServer(ResourceHandlers(cluster_cache, device=False))
        tenants = {d['metadata']['namespace'] for d in self.tenant_docs}
        texts = {f'{d["metadata"]["namespace"]}/{d["metadata"]["name"]}':
                 (d['spec']['rules'][0]['name'],
                  d['spec']['rules'][0]['validate']['message'])
                 for d in self.tenant_docs}
        worded = 0
        seen = {(kind, answer): 0 for kind in ('tenant', 'platform')
                for answer in (True, False)}
        problems, differing = [], 0
        verdicts = {}   # bodies repeat when the pool is short: judge once
        for k in sorted(self.record):
            body = self.bodies[k % len(self.bodies)]
            request = json.loads(body)['request']
            if body not in verdicts:
                failing = _reference.failing(
                    self.tenant_docs, request['object'],
                    request['namespace'])
                by_cluster = json.loads(host.handle(
                    '/validate/fail', body))['response']['allowed']
                verdicts[body] = (by_cluster and not failing, failing)
            want, failing = verdicts[body]
            response = json.loads(self.record[k][3])['response']
            got = response['allowed']
            kind = 'tenant' if request['namespace'] in tenants \
                else 'platform'
            seen[(kind, got)] += 1
            wrong = None if got or want else message_problem(
                response['status']['message'], request['namespace'],
                failing, texts)
            worded += not (got or want or wrong)
            if wrong:
                differing += 1
                problems.append(
                    f'the denial of request {k} in {request["namespace"]} '
                    f'{wrong}; the reference has {failing} failing: '
                    f'{response["status"]["message"][:600]!r}')
            if got != want:
                differing += 1
                problems.append(
                    f'request {k} in {request["namespace"]} was '
                    f'{"allowed" if got else "denied"}; the reference '
                    f'{"allows" if want else "denies"} it (tenant policies '
                    f'failing: {failing})')
        host.stop()
        say(f'check: allowed/denied of {len(self.record) - differing} of '
            f'{len(self.record)} answers equal the tenant reference\'s, '
            f'{worded} denial messages name its failing tenant policies in '
            f'their own words and no other tenant; '
            f'answers by namespace kind {[(k, a, n) for (k, a), n in seen.items()]} '
            f'({time.monotonic() - t0:.1f}s)')
        self.failed += differing
        for (kind, answer), n in seen.items():
            if not n:
                problems.append(f'no request in a {kind} namespace was '
                                f'{"allowed" if answer else "denied"}')
        return problems[:5]
