"""Driver of the admission webhook where every write is mutated, then
validated: the loops of ``drivers/webhook.py`` and the sweep of
``drivers/webhook_tenants.py``, with a set-up, a write and a check of its own.

A **write** is what the API server does with one CREATE or UPDATE of a Pod in
a cluster that has both kinds of policy: ``handle('/mutate/fail', review)``;
if that allows, the base64 JSONPatch of the answer is applied to the object
(with the plain reference's RFC 6902 applier, as the API server applies it);
then ``handle('/validate/fail', review of the patched object)``.  A write is
**timed from when it was due to the validate answer** (a ``/mutate`` denial
ends it there), one sample a write; the two legs' own latencies are kept for
the per-layer metrics.  ``attempted`` counts writes.

Installed are the committed validate packs as Enforce ``ClusterPolicy``
objects (not replicated) and the configuration's ``mutate_packs``.  A program
whose mutate set does not lower this pack to the device would answer every
``/mutate`` from the host loop; the run says so and ends before it builds
anything.

The check holds the run to the configuration's guarantees: **every**
``/mutate`` answer's patch, applied, gives the document the plain reference
(``benchmarks/reference/mutate_defaults.py``) gives, in canonical JSON byte
for byte; **every** write's allowed/denied equals that of the same handler
chain with ``device=False`` given the same mutated object; for a seeded
sample both answers' bytes equal those of the ``device=False`` server; every
``/mutate`` request was served by the compiled set and only the rows the
reference names fell back to the host engine; and the scanners set-up built
are the ones still serving.
"""

from __future__ import annotations

import base64
import collections
import copy
import json
import random
import time

import benchlib
import bytes_mutate
from benchlib import say

_webhook = benchlib.load_module('drivers', 'webhook')
_tenants = benchlib.load_module('drivers', 'webhook_tenants')
_reference = benchlib.load_module('reference', 'mutate_defaults')

MUTATE, VALIDATE = '/mutate/fail', '/validate/fail'

#: one write's record.  The base driver reads the first four by position:
#: ``answer`` is the one that decided the write (the validate answer, or the
#: ``/mutate`` denial that ended it); ``validated`` and ``validate_ms`` are
#: None where ``/mutate`` denied
Write = collections.namedtuple(
    'Write', 'due sent done answer mutated validated mutate_ms validate_ms')


def patched_review(body: bytes, answer: bytes):
    """``(allowed, body of the /validate review)`` for one ``/mutate``
    answer: the review with the answer's patch applied to its object, as the
    API server hands it on; None where the write was denied."""
    response = json.loads(answer)['response']
    if not response['allowed']:
        return False, None
    if 'patch' not in response:
        return True, body
    review = json.loads(body)
    review['request']['object'] = _reference.apply_patch(
        review['request']['object'],
        json.loads(base64.b64decode(response['patch'])))
    return True, json.dumps(review).encode()


class Driver(_webhook.Driver):
    sweep = _tenants.Driver.sweep
    _stages = _tenants.Driver._stages

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
        if not lowered.device_ok:
            raise RuntimeError(
                'this program does not lower the mutate pack to the device, '
                'so every /mutate would be answered by the host loop: ' +
                '; '.join(f'{p.policy}/{p.rule}: {p.reason} ({p.detail})'
                          for p in lowered.placements if p.reason))
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
            f'({lowered.n_sites} edit sites), {len(self.bodies)} request '
            f'bodies from a cluster of {len(cluster)} in '
            f'{time.monotonic() - t0:.1f}s')

        t0 = time.monotonic()
        self.failures = benchlib.FailureLog()
        self.cache = pcache.Cache()
        self.cache.warm_up(validate_policies + mutate_policies)
        self.handlers = ResourceHandlers(self.cache, serving_mode='batch')
        self.server = WebhookServer(self.handlers)
        installed = self.cache.get_installed(pcache.VALIDATE_ENFORCE, 'Pod')
        self.mutate_set = self.cache.get_policies(pcache.MUTATE, 'Pod',
                                                  'ns-0')
        if (len(installed), len(self.mutate_set)) != \
                (len(validate_policies), len(mutate_policies)):
            raise RuntimeError(
                f'{len(installed)} validate and {len(self.mutate_set)} '
                f'mutate policies apply to a Pod, not '
                f'{len(validate_policies)} and {len(mutate_policies)}')
        limit = float(self.config['guarantees']['ready_within_s'])
        for kind, policies in (('validate', installed),
                               ('mutate', self.mutate_set)):
            if not self.handlers.wait_device_ready(
                    policies, timeout=limit - (time.monotonic() - t0),
                    kind=kind):
                raise RuntimeError(
                    f'the compiled {kind} set did not come up in '
                    f'{limit:g}s: ' + '; '.join(
                        f'{b["state"]} after {b["failures"]} failures, '
                        f'last: {b.get("last_error", "")}'
                        for b in breaker.debug_report()['breakers']))
        self.installed = installed
        self.scanner = self.handlers._device_scanner(installed)
        self.mutate_scanner = self.handlers._device_scanner(self.mutate_set,
                                                            kind='mutate')
        say(f'set-up/build: {len(installed)} validate policies '
            f'({len(self.scanner.cps.programs)} rule programs, '
            f'{len(self.scanner.cps.host_rules)} host rules) and '
            f'{len(self.mutate_set)} mutate policies '
            f'({len(self.mutate_scanner.program.programs)} rule programs '
            f'on the device) ready in {time.monotonic() - t0:.1f}s')

        # the first writes of the pool, unmeasured, run both capacity-64
        # programs; the window starts behind them
        t0 = time.monotonic()
        self.next_index = self.traffic['warm_requests']
        for k in range(self.next_index):
            self._send(k, time.monotonic(), {})
        self.handlers._get_batcher().reset_stats()
        self._stages_before = self._stages()
        say(f'set-up/warm: {self.next_index} writes in '
            f'{time.monotonic() - t0:.1f}s')

    # -- one write ------------------------------------------------------------

    def _send(self, k: int, due: float, record: dict) -> None:
        body = self.bodies[k % len(self.bodies)]
        sent = time.monotonic()
        mutated = self.server.handle(MUTATE, body)
        between = time.monotonic()
        allowed, review = patched_review(body, mutated)
        validated = validate_ms = None
        if allowed:
            asked = time.monotonic()
            validated = self.server.handle(VALIDATE, review)
            validate_ms = (time.monotonic() - asked) * 1000.0
        record[k] = Write(due, sent, time.monotonic(), validated or mutated,
                          mutated, validated, (between - sent) * 1000.0,
                          validate_ms)

    def _reduce_open(self, record: dict) -> dict:
        window = super()._reduce_open(record)
        writes = [record[k] for k in self._due if k in record]
        self._samples['mutate_ms'] = [w.mutate_ms for w in writes]
        self._samples['validate_ms'] = [w.validate_ms for w in writes
                                        if w.validated is not None]
        return window

    # -- the check ------------------------------------------------------------

    def check(self) -> list:
        self._stage_counts = benchlib.delta(self._stages_before,
                                            self._stages())
        problems = super().check()
        problems += self._against_the_reference()
        problems += self._served_by_the_compiled_sets()
        return problems

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
            pod = json.loads(body)['request']['object']
            self._expected_fallbacks += _reference.expects_host(pod)
            wrong = []
            allowed, review = patched_review(body, mutated)
            if not allowed:
                wrong.append('/mutate denied it: ' + json.loads(mutated)[
                    'response']['status']['message'][:300])
            else:
                got = json.loads(review)['request']['object']
                if _reference.canonical(got) != _reference.canonical(
                        _reference.mutate(pod)):
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
                problems.append(f'write {k}: ' + '; '.join(wrong))
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
                f'/mutate requests were answered by the compiled set over '
                f'{stats["mutate_rows"]} rows (host loop: '
                f'{stats["mutate_host_loop_requests"]}, shed: '
                f'{stats["shed_total"]})')
        if stats['mutate_fallback_rows'] != self._expected_fallbacks:
            problems.append(
                f'{stats["mutate_fallback_rows"]} rows fell back to the '
                f'host engine; the reference names '
                f'{self._expected_fallbacks}')
        validated = sum(w.validated is not None
                        for w in self.record.values())
        if stats['device_path_requests'] != validated or \
                stats['host_loop_requests']:
            problems.append(
                f'{stats["device_path_requests"]} of {validated} /validate '
                f'requests were answered by the compiled path (host loop: '
                f'{stats["host_loop"]})')
        scanner = self.mutate_scanner
        program = scanner.program
        host_rules = [p for p in program.placements if p.reason]
        if not scanner.ok or len(program.programs) != len(self.mutate_set) \
                or host_rules:
            problems.append(f'the mutate set is not wholly on the device: '
                            f'{host_rules}')
        if stats['scanner_builds'] != 1 or \
                self.handlers._device_scanner(self.installed) \
                is not self.scanner or \
                self.handlers._device_scanner(self.mutate_set, kind='mutate') \
                is not scanner:
            problems.append(f'a scanner was built after set-up '
                            f'({stats["scanner_builds"]} validate builds)')
        # one more run of the capacity-64 mutate program, to see where its
        # outputs live and to count its bytes from the lanes it was given
        self._lanes = encode_mutate_batch(
            [copy.deepcopy(WARM_POD)], program,
            padded_n=self.scanner.SMALL_BATCH, width=scanner._width)
        with jax.enable_x64(True):
            outs = scanner._kernel._jitted(self._lanes)
        on = sorted({d.platform for out in outs for d in out.devices()})
        say(f'check: mutate kernel outputs on {on}; '
            f'{stats["mutate_dispatches"]} mutate and '
            f'{stats["validate_dispatches"]} validate dispatches, '
            f'{stats["mutate_fallback_rows"]} fallback rows')
        if on != [self.platform]:
            problems.append(f'the mutate kernel\'s outputs live on {on}, '
                            f'not on {self.platform!r}')
        return problems

    def counters(self) -> dict:
        out = super().counters()
        out['stages'] = stages = self._stage_counts
        out['requests']['mutate_offered'] = self.attempted
        pre, post = stages.get('mutate_pre', {}), stages.get('mutate_post',
                                                             {})
        out['mutate_handler'] = {
            'total_s': pre.get('total_s', 0.0) + post.get('total_s', 0.0),
            'count': pre.get('count', 0)}
        out['mutate_dispatch'] = bytes_mutate.describe(
            self._lanes, len(self.mutate_scanner.program.programs))
        return out
