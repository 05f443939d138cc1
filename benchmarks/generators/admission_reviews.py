"""Seeded AdmissionReview bodies for the webhook.

The requests of ``chip_smoke.py``'s admission phase (``compliant_pod``,
``admission_request``), which passed on the chip in PR 22: the cluster's own
Pods, nearly all of which break some enforce policy, with every third
replaced by a Pod that every policy of the pack admits; three in four CREATE,
the rest UPDATE with an ``oldObject`` that differs.  The user of each request
is drawn by Zipf from the seed, with the user model of
``kyverno_tpu/conformance/loadgen.py`` (``_zipf_cum``, ``_pick``,
``user_info``) copied here.  The i-th body is a pure function of
``(seed, i)`` and the cluster.
"""

import bisect
import json
import random


def compliant_pod(i: int) -> dict:
    """A Pod every policy of the pack admits (restricted PSS included)."""
    container = {
        'name': 'c0', 'image': 'ghcr.io/org/app:v2.1',
        'resources': {'requests': {'memory': '64Mi', 'cpu': '100m'},
                      'limits': {'memory': '128Mi'}},
        'livenessProbe': {'httpGet': {'path': '/healthz', 'port': 8080}},
        'securityContext': {'allowPrivilegeEscalation': False,
                            'runAsNonRoot': True,
                            'capabilities': {'drop': ['ALL']}}}
    return {'apiVersion': 'v1', 'kind': 'Pod',
            'metadata': {'name': f'ok-{i}', 'namespace': f'ns-{i % 7}',
                         'labels': {'app': f'app-{i % 11}', 'tier': 'web'}},
            'spec': {'securityContext': {
                         'runAsNonRoot': True,
                         'seccompProfile': {'type': 'RuntimeDefault'}},
                     'containers': [container]}}



def _zipf_cum(n: int, s: float) -> list:
    """Cumulative zipf(s) weights over ranks 1..n (rank 1 hottest)."""
    total, out = 0.0, []
    for k in range(1, n + 1):
        total += 1.0 / (k ** s)
        out.append(total)
    return out


def user_info(idx: int, teams: int) -> dict:
    groups = ['system:authenticated', f'team-{idx % teams}']
    if idx % 7 == 0:
        groups.append('system:masters')
    return {'username': f'user-{idx}', 'groups': groups}


def admission_request(i: int, doc: dict, info: dict,
                      update_every: int) -> dict:
    """The i-th request: CREATE, or UPDATE with an oldObject that differs."""
    request = {
        'uid': f'bench-{i}',
        'operation': 'UPDATE' if i % update_every == update_every - 1
        else 'CREATE',
        'kind': {'group': '', 'version': 'v1', 'kind': 'Pod'},
        'namespace': doc['metadata']['namespace'],
        'name': doc['metadata']['name'],
        'object': doc,
        'userInfo': info,
    }
    if request['operation'] == 'UPDATE':
        old = json.loads(json.dumps(doc))
        old['metadata'].setdefault('labels', {})['rev'] = 'old'
        request['oldObject'] = old
    return request


def generate(seed: int, cluster: list, count: int, users: int = 200,
             teams: int = 12, zipf_s: float = 1.1, compliant_every: int = 3,
             update_every: int = 4) -> list:
    """``count`` AdmissionReview bodies (bytes) over the cluster's Pods."""
    pods = [r for r in cluster if r['kind'] == 'Pod']
    if not pods:
        raise ValueError('the cluster holds no Pod to send')
    cum = _zipf_cum(users, zipf_s)
    bodies = []
    for i in range(count):
        doc = compliant_pod(i) if i % compliant_every == compliant_every - 1 \
            else pods[i % len(pods)]
        draw = random.Random((seed << 20) ^ i).random() * cum[-1]
        idx = min(bisect.bisect_left(cum, draw), users - 1)
        bodies.append(json.dumps({
            'apiVersion': 'admission.k8s.io/v1', 'kind': 'AdmissionReview',
            'request': admission_request(i, doc, user_info(idx, teams),
                                         update_every)}).encode())
    return bodies
