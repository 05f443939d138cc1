"""Seeded tenant policies: each tenant namespace carries a few namespaced
Enforce ``Policy`` objects of its own, instances of four public
``kyverno/policies`` best-practice templates with the tenant's parameter:

``require-team-label``         (``require-labels``) ``metadata.labels.team``
                               equals the tenant's name
``restrict-image-registries``  every container image matches
                               ``registry.example.com/<tenant>/*``
``limit-memory``               (``require-requests-limits``) every
                               container's ``resources.limits.memory`` is
                               ``<=`` the tenant's cap, by tenant index
``disallow-latest-tag``        no image ends in ``:latest``; no parameter,
                               so the tenants' instances differ only in the
                               namespace they live in

Every policy's message names its tenant, so no two policies word the same
message.  ``generate(seed, ...)`` is the entry the harness calls.  The tenants'
names and parameters are a function of their index alone, so that every seed
installs the set a compile cache has seen; what the seed chooses is which
tenants are busy (``rank_order``), and the requests draw on it.
"""

import random

MEMORY_CAPS = ['512Mi', '1Gi', '2Gi', '4Gi', '8Gi']
TEMPLATES = ['require-team-label', 'restrict-image-registries',
             'limit-memory', 'disallow-latest-tag']


def tenant_name(index: int) -> str:
    return f'tenant-{index:03d}'


def memory_cap(index: int) -> str:
    return MEMORY_CAPS[index % len(MEMORY_CAPS)]


def registry(tenant: str) -> str:
    return f'registry.example.com/{tenant}'


def rank_order(seed: int, namespaces: int) -> list:
    """Tenant indexes from the busiest down, in an order the seed chooses."""
    order = list(range(namespaces))
    random.Random((seed << 8) ^ 0x7E4A).shuffle(order)
    return order


def _policy(tenant: str, name: str, rule: str, message: str,
            pattern: dict) -> dict:
    return {
        'apiVersion': 'kyverno.io/v1', 'kind': 'Policy',
        'metadata': {
            'name': name, 'namespace': tenant,
            'annotations': {
                'pod-policies.kyverno.io/autogen-controllers': 'none'}},
        'spec': {
            'validationFailureAction': 'Enforce',
            'background': True,
            'rules': [{
                'name': rule,
                'match': {'any': [{'resources': {'kinds': ['Pod']}}]},
                'validate': {'message': message, 'pattern': pattern}}]}}


def tenant_policies(index: int, per_namespace: int = 4) -> list:
    """The first ``per_namespace`` templates as policy documents of tenant
    ``index``."""
    tenant = tenant_name(index)
    cap = memory_cap(index)
    docs = [
        _policy(tenant, 'require-team-label', 'check-team-label',
                f'Pods in {tenant} must carry the label team={tenant}.',
                {'metadata': {'labels': {'team': tenant}}}),
        _policy(tenant, 'restrict-image-registries', 'validate-registries',
                f'Images in {tenant} must come from {registry(tenant)}/.',
                {'spec': {'containers': [
                    {'image': f'{registry(tenant)}/*'}]}}),
        _policy(tenant, 'limit-memory', 'validate-memory-limit',
                f'Containers in {tenant} need a memory limit of at most '
                f'{cap}.',
                {'spec': {'containers': [
                    {'resources': {'limits': {'memory': f'<={cap}'}}}]}}),
        _policy(tenant, 'disallow-latest-tag', 'validate-image-tag',
                f'Images in {tenant} must not use the tag latest.',
                {'spec': {'containers': [{'image': '!*:latest'}]}}),
    ]
    return docs[:per_namespace]


def generate(seed: int, namespaces: int = 250, per_namespace: int = 4) -> list:
    """``namespaces`` x ``per_namespace`` policy documents, tenant by
    tenant.  The seed is not used: see the module's text."""
    del seed
    if not 1 <= per_namespace <= len(TEMPLATES):
        raise ValueError(f'per_namespace is 1..{len(TEMPLATES)}')
    return [doc for index in range(namespaces)
            for doc in tenant_policies(index, per_namespace)]
