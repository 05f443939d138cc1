"""Driver of the background controller when a platform team installs
policies with ``mutateExistingOnPolicyUpdate: true`` on a running cluster:
``PolicyController.add_policy`` for each policy of the pack, then
``UpdateRequestController.process_pending``, over a cluster held by the
program's in-memory client (``kyverno_tpu/dclient/client.py`` ``FakeClient``),
as ``kyverno_tpu/cmd/background_controller.py`` runs them.

Set-up generates the cluster and loads it into the client, and installs the
pack once on a warm cluster of its own (another client, other namespaces):
the install compiles the mutate-existing set and its device kernel at the
bulk capacity, which the measured install then finds built.  A program that
does not lower the pack's mutate-existing rules to the device ends the run
before it builds anything.

The traffic file's ``loop`` chooses what the window holds; ``policy_install``
is the install: the five ``add_policy`` calls (the policy controller lists
each policy's triggers and creates their UpdateRequests) and one
``process_pending`` (every pending UR, in one pass).  A pass cannot be cut
off, so the client stamps every target it writes, and the rate is the count
of target writes in the first ``seconds`` after the window opened over that
time (over the install's wall if it ended sooner).

The check holds the run to the configuration's guarantees: **every** target
the pack selects equals what the plain reference
(``benchmarks/reference/mutate_existing.py``) gives, in canonical JSON byte
for byte with ``resourceVersion`` and the last-applied annotation left out;
every UR is ``Completed``, one for each (policy, trigger) pair the reference
names; for a seeded sample of URs, the UR's status and every target it names
equal, byte for byte, those that the ``UpdateRequestController`` with
``device=False`` (the one-UR-at-a-time host path) gives from the run's own
URs of the sample's namespaces, in their order, on a cluster of those
namespaces (``resourceVersion`` and ``uid`` left out: the store assigns them
from a counter of the whole cluster); the targets the
device handed back are exactly the rows the reference names; and the
kernel's outputs live on the platform.
"""

from __future__ import annotations

import json
import random
import time

import benchlib
import bytes_mutate
from benchlib import say

_reference = benchlib.load_module('reference', 'mutate_existing')

#: the one policy of the pack whose rule the device does not lower (its
#: label value is a variable of the trigger): the per-policy placement's
#: witness
HOST_POLICY = 'label-existing-namespaces'


def _recording_client():
    """The program's in-memory client, stamping each write of a target."""
    from kyverno_tpu.dclient.client import FakeClient

    class Recording(FakeClient):
        def __init__(self):
            super().__init__()
            self.stamps = []

        def update_resource(self, api_version, kind, namespace, resource,
                            *args, **kwargs):
            out = super().update_resource(api_version, kind, namespace,
                                          resource, *args, **kwargs)
            if kind != 'UpdateRequest':
                self.stamps.append(time.monotonic())
            return out

    return Recording()


def _load(client, docs: list) -> None:
    for doc in docs:
        client.create_resource(doc['apiVersion'], doc['kind'],
                               doc['metadata'].get('namespace', ''), doc)


def _ur_key(ur: dict) -> tuple:
    res = ur['spec']['resource']
    return (ur['spec']['policy'], (res.get('apiVersion', ''), res['kind'],
                                   res.get('namespace', '') or '',
                                   res['name']))


def _sans_store(doc: dict) -> str:
    """Canonical JSON without what the store assigns from its counter."""
    doc = json.loads(json.dumps(doc))
    meta = doc.get('metadata') or {}
    meta.pop('resourceVersion', None)
    meta.pop('uid', None)
    return json.dumps(doc, sort_keys=True, separators=(',', ':'))


class Driver:
    def __init__(self, config, traffic, seed, seconds, platform, registry):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = seed, seconds
        self.platform, self.registry = platform, registry
        self.attempted = self.failed = 0

    # -- set-up ---------------------------------------------------------------

    def _controllers(self, client):
        from kyverno_tpu.background.update_request_controller import \
            UpdateRequestController
        from kyverno_tpu.engine.engine import Engine
        from kyverno_tpu.policy.controller import PolicyController
        by_name = {p.name: p for p in self.policies}
        engine = Engine()
        return (PolicyController(client, engine),
                UpdateRequestController(client, engine,
                                        policy_getter=by_name.get))

    def setup(self) -> None:
        t0 = time.monotonic()
        self.policies = benchlib.load_policies(self.config['packs'])
        try:
            from kyverno_tpu.mutate.plan import compile_mutate_set
            lowered = compile_mutate_set(self.policies, existing=True)
        except TypeError as e:
            raise RuntimeError(
                f'this program has no mutate-existing lowering ({e}), so '
                f'every target would be patched by the host engine') from e
        on_host = {p.policy for p in lowered.placements if p.reason}
        if on_host != {HOST_POLICY} or len(lowered.programs) != \
                len(self.policies) - 1:
            raise RuntimeError(
                f'every policy but {HOST_POLICY} has to be compiled to the '
                f'device; on the host: ' + '; '.join(
                    f'{p.policy}/{p.rule}: {p.reason} ({p.detail})'
                    for p in lowered.placements if p.reason))
        spec = self.config['cluster']
        generator = benchlib.load_module('generators', spec['generator'])
        self.generator = generator
        self.cluster = generator.generate(self.seed, **spec['params'])
        warm = generator.generate(self.seed + 1, self.traffic['warm_rows'],
                                  **self.config['warm']['params'])
        say(f'set-up/generate: {len(self.cluster)} resources, '
            f'{len(warm)} warm, {len(self.policies)} policies '
            f'({len(lowered.programs)} rules on the device, '
            f'{lowered.n_sites} edit sites) in '
            f'{time.monotonic() - t0:.1f}s')

        t0 = time.monotonic()
        warm_client = _recording_client()
        _load(warm_client, warm)
        policy_ctrl, ur_ctrl = self._controllers(warm_client)
        for policy in self.policies:
            policy_ctrl.add_policy(policy.raw)
        ur_ctrl.process_pending()
        left = [u for u in ur_ctrl.list_urs() if u.state != 'Completed']
        if left:
            raise RuntimeError(f'{len(left)} URs of the warm install are '
                               f'not Completed: {left[0].status}')
        say(f'set-up/warm: install on {len(warm)} resources, '
            f'{len(warm_client.stamps)} target writes in '
            f'{time.monotonic() - t0:.1f}s')

        t0 = time.monotonic()
        self.client = _recording_client()
        _load(self.client, self.cluster)
        self.stored = [obj for obj in self.client._store.values()]
        self.stored = json.loads(json.dumps(self.stored))
        self.policy_ctrl, self.ur_ctrl = self._controllers(self.client)
        say(f'set-up/build: {len(self.stored)} objects in the client in '
            f'{time.monotonic() - t0:.1f}s')

    # -- the window -----------------------------------------------------------

    def _stages(self) -> dict:
        from kyverno_tpu.observability import device as devtel
        return {dict(key).get('stage', ''): {'total_s': total,
                                             'count': count}
                for key, count, total in self.registry.histogram_series(
                    devtel.SCAN_STAGE_DURATION)}

    def _targets(self) -> dict:
        from kyverno_tpu.observability import device as devtel
        return {path: int(self.registry.counter_value(
            devtel.MUTATE_EXISTING_TARGETS, path=path))
            for path in ('device', 'fallback', 'host_policy')}

    def measure(self) -> dict:
        before = {'stages': self._stages(), 'mx_targets': self._targets()}
        loop = getattr(self, '_loop_' + self.traffic['loop'])
        start = time.monotonic()
        metrics = loop(start)
        end = time.monotonic()
        self._counters = benchlib.delta(before, {
            'stages': self._stages(), 'mx_targets': self._targets()})
        return {'start': start, 'end': end, 'metrics': metrics}

    def _loop_policy_install(self, start: float) -> dict:
        for policy in self.policies:
            self.policy_ctrl.add_policy(policy.raw)
        spawned = time.monotonic() - start
        self.ur_ctrl.process_pending()
        wall = time.monotonic() - start
        stamps = self.client.stamps
        span = min(self.seconds, wall)
        in_window = sum(t <= start + span for t in stamps)
        say(f'window: {len(self.policies)} policies installed, URs '
            f'spawned in {spawned:.1f}s; the pass wrote {len(stamps)} '
            f'targets, the install took {wall:.1f}s; {in_window} writes in '
            f'the first {span:.1f}s')
        return benchlib.yields(self.traffic, {'rate': in_window / span})

    # -- the check ------------------------------------------------------------

    def check(self) -> list:
        problems = []
        t0 = time.monotonic()
        final, requests = _reference.install(
            [p.raw for p in self.policies], self.stored)
        self.attempted = sum(len(keys) for _n, _t, keys in requests)
        store = self.client._store
        differing = [key for key, doc in final.items()
                     if _reference.comparable(doc) !=
                     _reference.comparable(store[key])]
        self.failed += len(differing)
        problems += [f'{key} is not what the reference gives'
                     for key in differing[:5]]
        urs = [u.raw for u in self.ur_ctrl.list_urs()]
        states = {}
        for ur in urs:
            state = (ur.get('status') or {}).get('state')
            states[state] = states.get(state, 0) + 1
        if sorted(_ur_key(ur) for ur in urs) != \
                sorted((name, tkey) for name, tkey, _keys in requests) or \
                set(states) != {'Completed'}:
            problems.append(f'{len(urs)} URs ({states}) for the '
                            f'{len(requests)} (policy, trigger) pairs the '
                            f'reference names')
            self.failed += len(urs) - states.get('Completed', 0)
        say(f'check: {len(final) - len(differing)} of {len(final)} targets '
            f'are the reference\'s, {len(urs)} URs {states} '
            f'({time.monotonic() - t0:.1f}s)')

        counts = self._counters['mx_targets']
        fallback = sum(_reference.expects_host(store[key])
                       for name, _t, keys in requests
                       if name == 'retrofit-pull-policy' for key in keys)
        host = sum(len(keys) for name, _t, keys in requests
                   if name == HOST_POLICY)
        device = self.attempted - fallback - host
        say(f'check: targets by path {counts}; the reference names '
            f'{device} device, {fallback} fallback, {host} host_policy')
        if counts != {'device': device, 'fallback': fallback,
                      'host_policy': host}:
            problems.append(f'targets by path {counts}, not the device '
                            f'{device}, fallback {fallback}, host_policy '
                            f'{host} the reference names')
        problems += self._against_the_host_path(urs, requests)
        problems += self._kernel_on_the_platform()
        return problems

    def _against_the_host_path(self, urs: list, requests: list) -> list:
        """A seeded sample of URs against the ``device=False`` controller
        on a cluster of the sample's namespaces, given the run's own URs
        there as the policy controller spawned them: their names, and so
        the order a pass takes them in (the last policy to patch a target
        writes its annotation), are the run's."""
        t0 = time.monotonic()
        rng = random.Random(self.seed + 2)
        sample = rng.sample(urs, min(self.config['check']['byte_sample'],
                                     len(urs)))
        targets = {(name, tkey): keys for name, tkey, keys in requests}

        def namespace_of(ur: dict) -> str:
            key = _ur_key(ur)[1]
            return key[3] if key[1] == 'Namespace' else key[2]

        namespaces = {namespace_of(u) for u in sample}
        docs = [d for d in self.cluster
                if (d['metadata']['name'] if d['kind'] == 'Namespace'
                    else d['metadata'].get('namespace')) in namespaces]
        from kyverno_tpu.background.update_request_controller import \
            UpdateRequestController
        from kyverno_tpu.engine.engine import Engine
        client = _recording_client()
        _load(client, docs)
        _load(client, [
            {'apiVersion': ur['apiVersion'], 'kind': ur['kind'],
             'metadata': {key: ur['metadata'][key] for key in
                          ('name', 'generateName', 'namespace', 'labels')
                          if key in ur['metadata']},
             'spec': ur['spec'], 'status': {'state': 'Pending'}}
            for ur in urs if namespace_of(ur) in namespaces])
        by_name = {p.name: p for p in self.policies}
        ur_ctrl = UpdateRequestController(client, Engine(),
                                          policy_getter=by_name.get,
                                          device=False)
        ur_ctrl.process_pending()
        host_urs = {_ur_key(u.raw): u.raw for u in ur_ctrl.list_urs()}
        problems, differing, compared = [], 0, 0
        for ur in sample:
            key = _ur_key(ur)
            want = host_urs.get(key)
            wrong = []
            if want is None:
                wrong.append('the host path has no such UR')
            elif json.dumps(want.get('status'), sort_keys=True) != \
                    json.dumps(ur.get('status'), sort_keys=True):
                wrong.append(f'status {ur.get("status")} where the host '
                             f'path gives {want.get("status")}')
            for tkey in targets.get(key, []):
                compared += 1
                if _sans_store(self.client._store[tkey]) != \
                        _sans_store(client._store[tkey]):
                    wrong.append(f'target {tkey} differs')
            if wrong:
                differing += 1
                problems.append(f'UR {key}: ' + '; '.join(wrong[:3]))
        say(f'check: {len(sample) - differing} of {len(sample)} sampled URs '
            f'and their {compared} targets equal the device=False '
            f'controller\'s byte for byte, on a cluster of {len(docs)} '
            f'({len(namespaces)} namespaces; {time.monotonic() - t0:.1f}s)')
        self.failed += differing
        return problems[:5]

    def _kernel_on_the_platform(self) -> list:
        import jax
        from kyverno_tpu.compiler.shapes import canonical_caps
        from kyverno_tpu.mutate.encode import encode_mutate_batch
        # the set as the pass compiled it: the policies by name
        scanner = self.ur_ctrl.mutate.scanner(
            sorted(self.policies, key=lambda p: p.name))
        self._lanes = encode_mutate_batch(
            self.cluster[:1], scanner.program,
            padded_n=canonical_caps()[-1], width=scanner._width)
        with jax.enable_x64(True):
            outs = scanner._kernel._jitted(self._lanes)
        on = sorted({d.platform for out in outs for d in out.devices()})
        self._rules = len(scanner.program.programs)
        say(f'check: mutate kernel outputs on {on}')
        if on != [self.platform]:
            return [f'the mutate kernel\'s outputs live on {on}, not on '
                    f'{self.platform!r}']
        return []

    def counters(self) -> dict:
        out = self._counters
        targets = out['mx_targets']
        targets['dispatched'] = targets['device'] + targets['fallback']
        targets['all'] = targets['dispatched'] + targets['host_policy']
        out['mutate_dispatch'] = bytes_mutate.describe(self._lanes,
                                                       self._rules)
        return out

    def close(self) -> None:
        pass
