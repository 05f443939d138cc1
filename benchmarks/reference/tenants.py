"""The plain reference of the tenant policies: which policies apply to a
request, and whether each of the four templates of
``generators/tenant_policies.py`` admits a Pod, straight from the template's
meaning.  It reads the policy documents for their parameters and imports
nothing of the program.

A cluster-wide policy applies everywhere; a namespaced ``Policy`` applies
only to a resource of its own namespace (upstream ``pkg/engine/validation.go``,
``policycache`` ``GetPolicies(type, kind, namespace)``).  A Pod is admitted by
the tenants' policies when every one that applies admits it; what the
cluster-wide policies say is not this file's to know.
"""

from fractions import Fraction

_BINARY = {'Ki': 2 ** 10, 'Mi': 2 ** 20, 'Gi': 2 ** 30, 'Ti': 2 ** 40,
           'Pi': 2 ** 50, 'Ei': 2 ** 60}
_DECIMAL = {'n': Fraction(1, 10 ** 9), 'u': Fraction(1, 10 ** 6),
            'm': Fraction(1, 1000), 'k': 10 ** 3, 'M': 10 ** 6, 'G': 10 ** 9,
            'T': 10 ** 12, 'P': 10 ** 15, 'E': 10 ** 18}


def quantity(text) -> Fraction:
    """A Kubernetes quantity as an exact number: ``128Mi``, ``0.5Gi``,
    ``500M``, ``100m``, ``1e3``, ``2``."""
    text = str(text).strip()
    for suffix, scale in _BINARY.items():
        if text.endswith(suffix):
            return Fraction(text[:-2]) * scale
    if text[-1:] in _DECIMAL and not text[-1:].isdigit():
        return Fraction(text[:-1]) * _DECIMAL[text[-1]]
    return Fraction(text)  # plain or with an exponent


def applies(policy: dict, namespace: str) -> bool:
    if policy['kind'] != 'Policy':
        return True
    return bool(namespace) and policy['metadata']['namespace'] == namespace


def _containers(pod: dict) -> list:
    return (pod.get('spec') or {}).get('containers') or []


def _image_pattern(policy: dict) -> str:
    rule = policy['spec']['rules'][0]
    return rule['validate']['pattern']['spec']['containers'][0]['image']


def team_label(policy: dict, pod: dict) -> bool:
    want = policy['spec']['rules'][0]['validate']['pattern'][
        'metadata']['labels']['team']
    labels = (pod.get('metadata') or {}).get('labels') or {}
    return labels.get('team') == want


def image_registry(policy: dict, pod: dict) -> bool:
    pattern = _image_pattern(policy)
    assert pattern.endswith('/*') and '*' not in pattern[:-1]
    prefix = pattern[:-1]
    return all(str(c.get('image', '')).startswith(prefix)
               and 'image' in c for c in _containers(pod))


def memory_limit(policy: dict, pod: dict) -> bool:
    pattern = policy['spec']['rules'][0]['validate']['pattern']['spec'][
        'containers'][0]['resources']['limits']['memory']
    assert pattern.startswith('<=')
    cap = quantity(pattern[2:])
    for c in _containers(pod):
        limit = ((c.get('resources') or {}).get('limits') or {}).get('memory')
        if limit is None or quantity(limit) > cap:
            return False
    return True


def not_latest(policy: dict, pod: dict) -> bool:
    assert _image_pattern(policy) == '!*:latest'
    return all('image' in c and not str(c['image']).endswith(':latest')
               for c in _containers(pod))


TEMPLATES = {'require-team-label': team_label,
             'restrict-image-registries': image_registry,
             'limit-memory': memory_limit,
             'disallow-latest-tag': not_latest}


def admits(policy: dict, pod: dict) -> bool:
    """Whether one tenant policy admits ``pod`` (which it applies to)."""
    return TEMPLATES[policy['metadata']['name']](policy, pod)


def failing(policies: list, pod: dict, namespace: str) -> list:
    """``namespace/name`` of the tenant policies that apply to ``pod`` in
    ``namespace`` and do not admit it."""
    return [f'{p["metadata"]["namespace"]}/{p["metadata"]["name"]}'
            for p in policies
            if applies(p, namespace) and not admits(p, pod)]
