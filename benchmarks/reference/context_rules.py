"""The plain reference of the four policies of ``packs/context.yaml``: what
each says of a resource, straight from its meaning over plain dicts.  It
reads the resource and the cluster's ConfigMaps and imports nothing of the
program.

``rows(resource, config_maps)`` gives ``[(policy, rule, result, message)]``
in the order of a report's rows (by policy, then by rule), ``result`` one of
``pass`` / ``fail`` / ``skip`` / ``error``.  ``message`` is given where the
row is an error (the message is then the engine's, word for word: a client
that cannot find a ConfigMap says so in ``absent_text``) and is ``None``
elsewhere: the wording of a pass, a fail or a skip is the host engine's to
hold (the sampled reports).

What the policies mean:

``allowed-pod-priorities``   a Pod's ``priorityClassName`` (none reads as
    ``''``) has to be among the classes its namespace is allowed: the key of
    that name in the ConfigMap ``default/allowed-pod-priorities``, a JSON
    array; a namespace without a key allows only Pods without a class.
``cm-array-example``   a Deployment's ``role`` annotation (none: ``''``) has
    to be among ``default/roles-dictionary`` ``allowed-roles``.
``exclude-namespaces-dynamically``   a Deployment or a Pod outside the
    namespaces of ``default/namespace-filters`` ``exclude`` needs the label
    ``foo``; inside them the rule is skipped.
``tenant-allowed-tiers``   a Pod's ``tier`` label, where it has one, has to
    be among the ``tiers`` of the ConfigMap ``tenant-policy`` of its own
    namespace; a namespace that never created it gets an error row, label or
    none.

A rule written for Pods reaches the Pod controllers through Kyverno's
autogen: under the name ``autogen-<rule>`` the Pod template of a DaemonSet,
Deployment, Job, StatefulSet, ReplicaSet or ReplicationController, under
``autogen-cronjob-<rule>`` that of a CronJob's job template; the namespace is
the controller's own.
"""

import json

_CONTROLLERS = ('DaemonSet', 'Deployment', 'Job', 'StatefulSet',
                'ReplicaSet', 'ReplicationController')


def absent_text(namespace: str, name: str) -> str:
    """What a cluster client says of a ConfigMap that is not there."""
    return f'ConfigMap "{namespace}/{name}" not found'


def index(config_maps: list) -> dict:
    """``{(namespace, name): data}`` of a list of ConfigMaps."""
    return {(c['metadata']['namespace'], c['metadata']['name']):
            c.get('data') or {} for c in config_maps}


def _pod_template(resource: dict):
    """``(rule prefix, template metadata, pod spec)`` of a resource that a
    Pod rule reaches, or None."""
    kind = resource['kind']
    if kind == 'Pod':
        return '', resource.get('metadata') or {}, resource.get('spec') or {}
    spec = resource.get('spec') or {}
    if kind in _CONTROLLERS:
        template = spec.get('template') or {}
        prefix = 'autogen-'
    elif kind == 'CronJob':
        # autogen re-roots a CronJob rule's ``spec`` to the job template's
        # Pod spec and its ``metadata`` to ``spec.template.metadata``, as
        # for the other controllers (upstream pkg/autogen convertRule): a
        # CronJob has nothing there, so its labels read as none
        pod = (((spec.get('jobTemplate') or {}).get('spec') or {})
               .get('template') or {}).get('spec') or {}
        return 'autogen-cronjob-', \
            (spec.get('template') or {}).get('metadata') or {}, pod
    else:
        return None
    return prefix, template.get('metadata') or {}, template.get('spec') or {}


def _allowed(text: str) -> list:
    """A ConfigMap value as the list it spells: a JSON array of strings,
    or the one string it is."""
    try:
        parsed = json.loads(text)
    except ValueError:
        return [text]
    if isinstance(parsed, list) and all(isinstance(x, str) for x in parsed):
        return parsed
    return [text]


def _within(value: str, text: str) -> bool:
    return value == text or value in _allowed(text)


def rows(resource: dict, maps: dict) -> list:
    """The four policies' rows for one resource; ``maps`` is ``index()``'s."""
    out = []
    meta = resource.get('metadata') or {}
    namespace = meta.get('namespace', '')
    kind = resource['kind']
    template = _pod_template(resource)

    if template is not None:
        prefix, _tmeta, pod = template
        wanted = pod.get('priorityClassName') or ''
        allowed = maps[('default', 'allowed-pod-priorities')] \
            .get(namespace, '')
        out.append(('allowed-pod-priorities',
                    prefix + 'validate-pod-priority',
                    'pass' if _within(wanted, allowed) else 'fail', None))

    if kind == 'Deployment':
        role = (meta.get('annotations') or {}).get('role') or ''
        allowed = maps[('default', 'roles-dictionary')]['allowed-roles']
        out.append(('cm-array-example', 'validate-role-annotation',
                    'pass' if _within(role, allowed) else 'fail', None))

    if kind in ('Deployment', 'Pod'):
        excluded = maps[('default', 'namespace-filters')]['exclude']
        if _within(namespace, excluded):
            result = 'skip'
        else:
            result = 'pass' if (meta.get('labels') or {}).get('foo') \
                is not None else 'fail'
        out.append(('exclude-namespaces-dynamically',
                    'exclude-namespaces-dynamically', result, None))

    if template is not None:
        prefix, tmeta, _pod = template
        rule = prefix + 'validate-tier'
        tenant = maps.get((namespace, 'tenant-policy'))
        tier = (tmeta.get('labels') or {}).get('tier') or ''
        if tenant is None:
            out.append(('tenant-allowed-tiers', rule, 'error',
                        'failed to load context: failed to retrieve config '
                        'map for context entry tenantpolicy: '
                        + absent_text(namespace, 'tenant-policy')))
        elif tier == '':
            out.append(('tenant-allowed-tiers', rule, 'skip', None))
        else:
            out.append(('tenant-allowed-tiers', rule,
                        'pass' if _within(tier, tenant['tiers'])
                        else 'fail', None))
    return out


def load_failed(resource: dict, maps: dict) -> list:
    """The ``(policy, rule)`` cells of a resource whose context load fails:
    what the device path has to hand to the host."""
    return [(policy, rule) for policy, rule, result, _m
            in rows(resource, maps) if result == 'error']
