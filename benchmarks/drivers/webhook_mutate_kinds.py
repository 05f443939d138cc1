"""Driver of the admission webhook where every write of any of the seven kinds
a Pod policy's autogen reaches is mutated, then validated: the writes, loops
and sweep of ``drivers/webhook_mutate.py``, with a set-up and a check of its
own for the several compiled sets the kinds key.

The webhook compiles one program per set of policies that applies to a kind
(``Cache.get_installed`` on the validate leg, ``Cache.get_policies`` on the
mutate leg): a Pod and a controller see different sets (the JSON patch of
``annotate-revision`` is not converted by autogen; most validate policies
turn autogen off), so set-up builds and warms every set the seven kinds
name, and the warm writes reach each kind.  A program whose mutate set does
not lower this pack to the device would answer every ``/mutate`` from the
host loop; the run says so and ends before it builds anything.

The check holds the run to the configuration's guarantees, as the base
driver's does, against ``benchmarks/reference/mutate_foreach_kinds.py``:
**every** ``/mutate`` answer's patch, applied, gives the reference's
document; **every** write's allowed/denied equals the ``device=False``
chain's on the same mutated object; for a seeded sample both answers' bytes
equal those of the ``device=False`` server; every ``/mutate`` was served by
a compiled set and only the rows the reference names fell back; some rows
took a template path; and the scanners set-up built are the ones still
serving.  ``mutate_dispatch`` is the bytes of one dispatch of each mutate
program, weighted by the dispatches each served in the window.
"""

from __future__ import annotations

import copy
import json
import random
import time

import benchlib
import bytes_mutate
from benchlib import say

_base = benchlib.load_module('drivers', 'webhook_mutate')
_reference = benchlib.load_module('reference', 'mutate_foreach_kinds')
_chart = benchlib.load_module('generators', 'chart_cluster')

MUTATE, VALIDATE = _base.MUTATE, _base.VALIDATE
patched_review = _base.patched_review
#: bytes a row and rule of the fourth output a foreach program's kernel
#: returns (``elem``, int32), beside the three ``bytes_mutate`` counts
ELEM_BYTES = 4


class Driver(_base.Driver):

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from kyverno_tpu.api.policy import Policy
        from kyverno_tpu.mutate import compile_mutate_set
        from kyverno_tpu.policycache import cache as pcache
        from kyverno_tpu.serving import breaker
        from kyverno_tpu.webhooks.handlers import ResourceHandlers
        from kyverno_tpu.webhooks.server import WebhookServer
        t0 = time.monotonic()
        validate_policies = []
        for policy in benchlib.load_policies(self.config['packs']):
            doc = copy.deepcopy(policy.raw)
            doc.setdefault('spec', {})['validationFailureAction'] = 'Enforce'
            validate_policies.append(Policy(doc))
        mutate_policies = benchlib.load_policies(self.config['mutate_packs'])
        lowered = compile_mutate_set(mutate_policies)
        host_rules = [p for p in lowered.placements if p.reason]
        if not lowered.device_ok or host_rules:
            raise RuntimeError(
                'this program does not lower the mutate pack to the device, '
                'so every /mutate would be answered by the host loop: ' +
                '; '.join(f'{p.policy}/{p.rule}: {p.reason} ({p.detail})'
                          for p in host_rules))
        spec = self.config['cluster']
        cluster = benchlib.load_module(
            'generators', spec['generator']).generate(self.seed,
                                                      **spec['params'])
        spec = self.config['requests']
        self.bodies = benchlib.load_module(
            'generators', spec['generator']).generate(
                self.seed, cluster, spec['pool'], **spec['params'])
        say(f'set-up/generate: {len(validate_policies)} validate and '
            f'{len(mutate_policies)} mutate policies '
            f'({len(lowered.programs)} rule programs, {lowered.n_sites} '
            f'edit sites), {len(self.bodies)} request bodies from a cluster '
            f'of {len(cluster)} in {time.monotonic() - t0:.1f}s')

        t0 = time.monotonic()
        self.failures = benchlib.FailureLog()
        self.cache = pcache.Cache()
        self.cache.warm_up(validate_policies + mutate_policies)
        self.handlers = ResourceHandlers(self.cache, serving_mode='batch')
        self.server = WebhookServer(self.handlers)
        # the distinct sets the seven kinds key, each once
        self.sets = {}
        for kind in _chart.KINDS:
            for what, policies in (
                    ('validate', self.cache.get_installed(
                        pcache.VALIDATE_ENFORCE, kind)),
                    ('mutate', self.cache.get_policies(
                        pcache.MUTATE, kind, 'ns-0'))):
                key = (what, tuple(p.name for p in policies))
                self.sets.setdefault(key, policies)
        limit = float(self.config['guarantees']['ready_within_s'])
        for (what, _names), policies in self.sets.items():
            if not self.handlers.wait_device_ready(
                    policies, timeout=limit - (time.monotonic() - t0),
                    kind=what):
                raise RuntimeError(
                    f'the compiled {what} set of {len(policies)} policies '
                    f'did not come up in {limit:g}s: ' + '; '.join(
                        f'{b["state"]} after {b["failures"]} failures, '
                        f'last: {b.get("last_error", "")}'
                        for b in breaker.debug_report()['breakers']))
        self.scanners = {key: self.handlers._device_scanner(policies, key[0])
                         for key, policies in self.sets.items()}
        pod_validate = self.cache.get_installed(pcache.VALIDATE_ENFORCE,
                                                'Pod')
        self.installed = pod_validate
        self.scanner = self.handlers._device_scanner(pod_validate)
        self.mutate_set = self.cache.get_policies(pcache.MUTATE, 'Pod', 'ns-0')
        self.mutate_scanner = self.handlers._device_scanner(self.mutate_set,
                                                            kind='mutate')
        say('set-up/build: ' + '; '.join(
            f'{what} set of {len(names)} policies ('
            + (f'{len(s.program.programs)} rule programs on the device'
               if what == 'mutate' else
               f'{len(s.cps.programs)} rule programs, '
               f'{len(s.cps.host_rules)} host rules') + ')'
            for (what, names), s in self.scanners.items())
            + f' ready in {time.monotonic() - t0:.1f}s')

        # the dispatches each mutate program serves, for the weighted bytes
        self._mutate_dispatches = {}
        for key, scanner in self.scanners.items():
            if key[0] == 'mutate':
                self._count_dispatches(key, scanner)

        # the first writes of the pool, unmeasured, reach every kind and run
        # every capacity-64 program; the window starts behind them
        t0 = time.monotonic()
        self.next_index = self.traffic['warm_requests']
        for k in range(self.next_index):
            self._send(k, time.monotonic(), {})
        self.handlers._get_batcher().reset_stats()
        self._mutate_dispatches = dict.fromkeys(self._mutate_dispatches, 0)
        self._stages_before = self._stages()
        say(f'set-up/warm: {self.next_index} writes in '
            f'{time.monotonic() - t0:.1f}s')

    def _count_dispatches(self, key, scanner) -> None:
        scan = scanner.scan
        self._mutate_dispatches[key] = 0

        def counted(*args, **kwargs):
            self._mutate_dispatches[key] += 1
            return scan(*args, **kwargs)

        scanner.scan = counted

    # -- the check ------------------------------------------------------------

    def _against_the_reference(self) -> list:
        """Every write's patch against the plain reference, its
        allowed/denied against the ``device=False`` chain on the same
        mutated object, and a seeded sample's two answers byte for byte."""
        from kyverno_tpu.webhooks.handlers import ResourceHandlers
        from kyverno_tpu.webhooks.server import WebhookServer
        t0 = time.monotonic()
        host = WebhookServer(ResourceHandlers(self.cache, device=False))
        answered = sorted(self.record)
        sample = set(random.Random(self.seed + 2).sample(
            answered, min(self.config['check']['byte_sample'],
                          len(answered))))
        problems, differing, judged = [], 0, {}
        self._expected_fallbacks = 0
        for k in answered:
            body = self.bodies[k % len(self.bodies)]
            mutated, validated = self.record[k].mutated, \
                self.record[k].validated
            obj = json.loads(body)['request']['object']
            self._expected_fallbacks += _reference.expects_host(obj)
            wrong = []
            allowed, review = patched_review(body, mutated)
            if not allowed:
                wrong.append('/mutate denied it: ' + json.loads(mutated)[
                    'response']['status']['message'][:300])
            else:
                got = json.loads(review)['request']['object']
                if _reference.canonical(got) != _reference.canonical(
                        _reference.mutate(obj)):
                    wrong.append('the patched document is not the '
                                 'reference\'s')
                if review not in judged:   # bodies repeat in a short pool
                    judged[review] = host.handle(VALIDATE, review)
                want = judged[review]
                if json.loads(validated)['response']['allowed'] != \
                        json.loads(want)['response']['allowed']:
                    wrong.append('allowed/denied differs from the '
                                 'device=False chain\'s on the same '
                                 'mutated object')
                if k in sample:
                    if host.handle(MUTATE, body) != mutated:
                        wrong.append('the /mutate answer\'s bytes differ '
                                     'from the device=False server\'s')
                    if want != validated:
                        wrong.append('the /validate answer\'s bytes differ '
                                     'from the device=False server\'s')
            if wrong:
                differing += 1
                problems.append(f'write {k} ({obj["kind"]}): ' +
                                '; '.join(wrong))
        host.stop()
        say(f'check: {len(answered) - differing} of {len(answered)} writes '
            f'carry the reference\'s patch and the device=False chain\'s '
            f'verdict on the patched object, {len(sample)} of them both '
            f'answers byte for byte; the reference hands '
            f'{self._expected_fallbacks} of them to the host engine '
            f'({time.monotonic() - t0:.1f}s)')
        self.failed += differing
        return problems[:5]

    def _served_by_the_compiled_sets(self) -> list:
        import jax
        from kyverno_tpu.compiler.scan import WARM_POD
        from kyverno_tpu.mutate.encode import encode_mutate_batch
        stats, problems = self._stats, []
        writes = len(self.record)
        if stats['mutate_device_path_requests'] != writes or \
                stats['mutate_host_loop_requests'] or stats['shed_total'] \
                or stats['mutate_rows'] != writes:
            problems.append(
                f'{stats["mutate_device_path_requests"]} of {writes} '
                f'/mutate requests were answered by the compiled sets over '
                f'{stats["mutate_rows"]} rows (host loop: '
                f'{stats["mutate_host_loop_requests"]}, shed: '
                f'{stats["shed_total"]})')
        if stats['mutate_fallback_rows'] != self._expected_fallbacks:
            problems.append(
                f'{stats["mutate_fallback_rows"]} rows fell back to the '
                f'host engine; the reference names '
                f'{self._expected_fallbacks}')
        if not stats['mutate_rows_template'] + stats['mutate_rows_cronjob']:
            problems.append('no row of a controller kind reached the device')
        validated = sum(w.validated is not None
                        for w in self.record.values())
        if stats['device_path_requests'] != validated or \
                stats['host_loop_requests']:
            problems.append(
                f'{stats["device_path_requests"]} of {validated} /validate '
                f'requests were answered by the compiled path (host loop: '
                f'{stats["host_loop"]})')
        validate_sets = sum(what == 'validate' for what, _n in self.sets)
        if stats['scanner_builds'] != validate_sets or any(
                self.handlers._device_scanner(policies, key[0])
                is not self.scanners[key]
                for key, policies in self.sets.items()):
            problems.append(f'a scanner was built after set-up '
                            f'({stats["scanner_builds"]} validate builds '
                            f'for {validate_sets} sets)')
        # one more run of each capacity-64 mutate program, to see where its
        # outputs live and to count its bytes from the lanes it was given
        self._mutate_bytes = []
        on = set()
        for key, scanner in self.scanners.items():
            if key[0] != 'mutate':
                continue
            program = scanner.program
            host_rules = [p for p in program.placements if p.reason]
            if not scanner.ok or host_rules:
                problems.append(f'the mutate set {key[1]} is not wholly on '
                                f'the device: {host_rules}')
                continue
            lanes = encode_mutate_batch(
                [copy.deepcopy(WARM_POD)], program,
                padded_n=self.scanner.SMALL_BATCH, width=scanner._width)
            with jax.enable_x64(True):
                outs = scanner._kernel._jitted(lanes)
            on |= {d.platform for out in outs for d in out.devices()}
            described = bytes_mutate.describe(lanes, len(program.programs))
            if scanner._kernel.has_foreach:
                described['bytes'] += described['capacity'] * \
                    len(program.programs) * ELEM_BYTES
            self._mutate_bytes.append(
                (self._mutate_dispatches.get(key, 0), described['bytes']))
            if scanner is self.mutate_scanner:
                self._lanes = lanes
        say(f'check: mutate kernel outputs on {sorted(on)}; '
            f'{stats["mutate_dispatches"]} mutate and '
            f'{stats["validate_dispatches"]} validate dispatches, '
            f'{stats["mutate_fallback_rows"]} fallback rows; mutate rows by '
            f'path pod / template / cronjob {stats["mutate_rows_pod"]} / '
            f'{stats["mutate_rows_template"]} / '
            f'{stats["mutate_rows_cronjob"]}')
        if sorted(on) != [self.platform]:
            problems.append(f'the mutate kernels\' outputs live on '
                            f'{sorted(on)}, not on {self.platform!r}')
        return problems

    def counters(self) -> dict:
        out = super().counters()
        dispatches = sum(n for n, _b in self._mutate_bytes)
        if dispatches:
            out['mutate_dispatch']['bytes'] = sum(
                n * b for n, b in self._mutate_bytes) / dispatches
        stats = out['batcher']
        out['mutate_paths'] = {
            'controller_rows': stats['mutate_rows_template'] +
            stats['mutate_rows_cronjob']}
        return out
