"""Seeded resource dump of ``BASELINE.json`` ``configs[4]`` (mutate and
generate with ``foreach`` over a cluster dump), for ``packs/config5.yaml``:
the cluster's Pods of ``mixed_cluster.py``'s ``make_pod`` (imported, not
copied), about 1 Namespace for every 50 of them, as a real dump holds, and
on about 3 Pods in 10 every container already ``imagePullPolicy: Always``.

``make_resource(rng, i)`` is the i-th resource, drawing from ``rng``;
``generate(seed, n)`` the first ``n``.
"""

import random

import benchlib

_mixed = benchlib.load_module('generators', 'mixed_cluster')


def make_resource(rng, i: int) -> dict:
    """The i-th resource of the dump."""
    if i % 50 == 49:
        return {'apiVersion': 'v1', 'kind': 'Namespace',
                'metadata': {'name': f'team-{i // 50}'}}
    pod = _mixed.make_pod(rng, i)
    if rng.random() < 0.3:
        for cont in pod['spec']['containers']:
            cont['imagePullPolicy'] = 'Always'
    return pod


def generate(seed: int, n: int) -> list:
    rng = random.Random(seed)
    return [make_resource(rng, i) for i in range(n)]
