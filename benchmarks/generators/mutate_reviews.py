"""Seeded AdmissionReview bodies for a cluster that sets defaults by mutation.

Pods as ``admission_reviews.py`` sends them (the cluster's own Pods, every
``compliant_every``-th replaced by the Pod every validate policy admits;
three in four CREATE, the rest UPDATE with an ``oldObject`` that differs; the
user by Zipf over the same user model), each then shaped, from the seed, in
the fields the defaults pack (``packs/mutate-defaults.yaml``) writes, so that
every one of its edit sites sometimes edits and sometimes finds its work
done:

- every Pod carries ``metadata.annotations`` (the JSON patch of
  ``stamp-annotations`` adds a key to it);
- 1 Pod in ``five_every`` has 5 containers, more than the device's element
  slots: the device hands its chain to the host engine.  Such a Pod is one of
  the cluster's own, never the compliant one;
- half of the containers carry ``imagePullPolicy``, half of those already
  ``Always``; a third carry ``resources.requests`` with ``memory``, ``cpu``
  or both, the others none (their limits stay), so that it is the mutation
  that makes the compliant Pod pass ``require-resources``;
- half of the Pods carry a pod-level ``securityContext`` holding some of the
  four keys ``add-default-securitycontext`` adds, with non-root values of
  their own; half carry a ``cost-center`` label; a third carry
  ``dnsPolicy: Default``.

After mutation the compliant Pods pass all the validate policies and the
cluster's own still break one (none of the defaults gives them a
``seccompProfile``), so both answers occur.  The i-th body is a pure function
of ``(seed, i)`` and the cluster.
"""

import bisect
import copy
import json
import random

import benchlib

_reviews = benchlib.load_module('generators', 'admission_reviews')

FIVE = 5
_CONTEXT_VALUES = {'runAsNonRoot': [True], 'runAsUser': [1001, 2000],
                   'runAsGroup': [3000, 4000], 'fsGroup': [2000, 5000]}
_REQUESTS = [{'memory': '64Mi'}, {'cpu': '250m'},
             {'memory': '128Mi', 'cpu': '100m'}]


def five_containers(i: int, five_every: int) -> bool:
    return i % five_every == 0


def compliant(i: int, compliant_every: int, five_every: int) -> bool:
    return i % compliant_every == compliant_every - 1 \
        and not five_containers(i, five_every)


def shape(rng, pod: dict, i: int, five: bool) -> dict:
    """``pod`` (a copy the caller owns) with the seeded mix of present and
    absent defaults."""
    meta, spec = pod['metadata'], pod['spec']
    meta.setdefault('annotations', {})['owner'] = f'team-{i % 5}'
    containers = spec['containers']
    if five:
        for j in range(len(containers), FIVE):
            extra = copy.deepcopy(containers[j % len(containers)])
            extra['name'] = f'c{j}'
            containers.append(extra)
    for container in containers:
        container.pop('imagePullPolicy', None)
        if rng.random() < 0.5:
            container['imagePullPolicy'] = 'Always' if rng.random() < 0.5 \
                else 'IfNotPresent'
        resources = container.get('resources') or {}
        resources.pop('requests', None)
        if rng.random() < 1 / 3:
            resources['requests'] = dict(rng.choice(_REQUESTS))
        if resources:
            container['resources'] = resources
        else:
            container.pop('resources', None)
    if rng.random() < 0.5:
        context = spec.setdefault('securityContext', {})
        keys = rng.sample(sorted(_CONTEXT_VALUES), rng.randint(1, 3))
        for key in sorted(keys):
            context[key] = rng.choice(_CONTEXT_VALUES[key])
    if rng.random() < 0.5:
        meta.setdefault('labels', {})['cost-center'] = f'cc-{i % 9}'
    if rng.random() < 1 / 3:
        spec['dnsPolicy'] = 'Default'
    return pod


def generate(seed: int, cluster: list, count: int, users: int = 200,
             teams: int = 12, zipf_s: float = 1.1, compliant_every: int = 3,
             update_every: int = 4, five_every: int = 25) -> list:
    """``count`` AdmissionReview bodies (bytes) for ``/mutate``."""
    pods = [r for r in cluster if r['kind'] == 'Pod']
    if not pods:
        raise ValueError('the cluster holds no Pod to send')
    cum = _reviews._zipf_cum(users, zipf_s)
    bodies = []
    for i in range(count):
        rng = random.Random((seed << 20) ^ i)
        user = min(bisect.bisect_left(cum, rng.random() * cum[-1]),
                   users - 1)
        five = five_containers(i, five_every)
        doc = _reviews.compliant_pod(i) \
            if compliant(i, compliant_every, five_every) \
            else copy.deepcopy(pods[i % len(pods)])
        bodies.append(json.dumps({
            'apiVersion': 'admission.k8s.io/v1', 'kind': 'AdmissionReview',
            'request': _reviews.admission_request(
                i, shape(rng, doc, i, five),
                _reviews.user_info(user, teams), update_every)}).encode())
    return bodies
