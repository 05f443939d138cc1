"""Seeded AdmissionReview bodies for a multi-tenant cluster.

Pods as ``admission_reviews.py`` sends them (three in four CREATE, the rest
UPDATE with an ``oldObject`` that differs; the user by Zipf over the same
user model), each into a namespace: by Zipf over the tenants in a rank order
the seed chooses (``tenant_policies.rank_order``), and now and then into one
of a few platform namespaces that have no policies of their own.

Every ``compliant_every``-th Pod is admitted by every policy that applies to
it.  Of the rest, half are the cluster's own Pods moved into the namespace,
which break a cluster policy as they do in ``admission-1k-enforce``, and half
are compliant but for one thing that only a tenant's policy forbids: the
wrong ``team`` label, a memory limit above the tenant's cap, an image from a
public registry, or an image from ANOTHER tenant's registry -- which that
tenant's policy would admit and this namespace's must deny.  In a platform
namespace no tenant policy applies, so those are admitted there.

The i-th body is a pure function of ``(seed, i)`` and the cluster.
"""

import bisect
import copy
import json
import random

import benchlib

_reviews = benchlib.load_module('generators', 'admission_reviews')
_tenants = benchlib.load_module('generators', 'tenant_policies')

#: memory limits every cap admits, in the suffixes Kubernetes reads
_UNDER_EVERY_CAP = ['128Mi', '256Mi', '0.25Gi', '500M', '512Mi']
#: per cap, a limit just above it (``tenant_policies.MEMORY_CAPS`` order)
_OVER_CAP = ['513Mi', '1.5Gi', '2049Mi', '5G', '16Gi']


def platform_namespace(k: int) -> str:
    return f'platform-{k}'


def tenant_pod(i: int, namespace: str, team: str, registry: str,
               memory: str) -> dict:
    """``admission_reviews.compliant_pod`` in ``namespace``, with the label,
    the registry and the memory limit a tenant's policies look at."""
    pod = _reviews.compliant_pod(i)
    pod['metadata']['namespace'] = namespace
    pod['metadata']['labels']['team'] = team
    container = pod['spec']['containers'][0]
    container['image'] = f'{registry}/app-{i % 11}:v2.{i % 7}'
    container['resources']['limits']['memory'] = memory
    return pod


def generate(seed: int, cluster: list, count: int, namespaces: int = 250,
             platform_namespaces: int = 10, platform_share: float = 0.05,
             users: int = 200, teams: int = 12, zipf_s: float = 1.1,
             compliant_every: int = 3, update_every: int = 4) -> list:
    """``count`` AdmissionReview bodies (bytes)."""
    pods = [r for r in cluster if r['kind'] == 'Pod']
    if not pods:
        raise ValueError('the cluster holds no Pod to send')
    user_cum = _reviews._zipf_cum(users, zipf_s)
    tenant_cum = _reviews._zipf_cum(namespaces, zipf_s)
    order = _tenants.rank_order(seed, namespaces)
    bodies = []
    for i in range(count):
        rng = random.Random((seed << 20) ^ i)
        user = min(bisect.bisect_left(user_cum, rng.random() * user_cum[-1]),
                   users - 1)
        if rng.random() < platform_share:
            index = None
            namespace = platform_namespace(rng.randrange(platform_namespaces))
            team, registry = 'platform', 'registry.example.com/platform'
        else:
            rank = min(bisect.bisect_left(
                tenant_cum, rng.random() * tenant_cum[-1]), namespaces - 1)
            index = order[rank]
            namespace = team = _tenants.tenant_name(index)
            registry = _tenants.registry(namespace)
        memory = rng.choice(_UNDER_EVERY_CAP)
        shape = i % compliant_every
        if shape == compliant_every - 1:
            doc = tenant_pod(i, namespace, team, registry, memory)
        elif shape % 2 == 0:
            doc = copy.deepcopy(pods[i % len(pods)])
            doc['metadata']['namespace'] = namespace
        else:
            other = _tenants.tenant_name(
                ((index or 0) + 1 + rng.randrange(namespaces - 1))
                % namespaces)
            breaks = rng.randrange(4)
            if breaks == 0:
                team = other
            elif breaks == 1:
                registry = _tenants.registry(other)
            elif breaks == 2:
                registry = 'ghcr.io/org'
            else:
                memory = _OVER_CAP[(index or 0) % len(_OVER_CAP)]
            doc = tenant_pod(i, namespace, team, registry, memory)
        bodies.append(json.dumps({
            'apiVersion': 'admission.k8s.io/v1', 'kind': 'AdmissionReview',
            'request': _reviews.admission_request(
                i, doc, _reviews.user_info(user, teams),
                update_every)}).encode())
    return bodies
