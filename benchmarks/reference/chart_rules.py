"""The plain reference of the 22 rules of ``packs/chart.yaml``, the Helm
chart ``kyverno-policies`` (baseline, restricted, and ``other``'s
``require-non-root-groups``): what each says of a resource, straight from
its meaning over plain dicts.  It reads the resource and imports nothing of
the program.

``rows(resource)`` gives ``[(policy, rule, result)]`` in the order of a
report's rows (by policy, then by rule), ``result`` one of ``pass`` /
``fail`` / ``skip``; a kind the chart does not reach gives none.  The
wording of a row is the host engine's to hold (the sampled reports).

A rule written for Pods reaches the Pod controllers through Kyverno's
autogen: under the name ``autogen-<rule>`` the Pod template of a DaemonSet,
Deployment, Job, StatefulSet, ReplicaSet or ReplicationController
(``spec.template``), under ``autogen-cronjob-<rule>`` that of a CronJob's job
template (``spec.jobTemplate.spec.template``).  What a rule says of the
template is what it says of the Pod.

What the rules mean, over the Pod's three container lists
(``ephemeralContainers``, ``initContainers``, ``containers``) and its own
``securityContext``: a field "may" be unset and, where set, has to be as the
rule says (the ``=(…)`` anchor); a field that "must" has to be set so; a
forbidden field has to be absent (``X(…)``).  None of the rules can be
skipped by a resource of these kinds in a background scan: the one
precondition (``request.operation`` is not ``DELETE``) always holds there,
an ``=(…)`` anchor passes where its field is absent, and a Pod always has a
container for the ``foreach`` rules to visit.
"""

import fnmatch

_CONTROLLERS = ('DaemonSet', 'Deployment', 'Job', 'StatefulSet',
                'ReplicaSet', 'ReplicationController')

ALLOWED_CAPABILITIES = {
    'AUDIT_WRITE', 'CHOWN', 'DAC_OVERRIDE', 'FOWNER', 'FSETID', 'KILL',
    'MKNOD', 'NET_BIND_SERVICE', 'SETFCAP', 'SETGID', 'SETPCAP', 'SETUID',
    'SYS_CHROOT'}
SELINUX_TYPES = {'container_t', 'container_init_t', 'container_kvm_t'}
SECCOMP_TYPES = {'RuntimeDefault', 'Localhost'}
SAFE_SYSCTLS = {
    'kernel.shm_rmid_forced', 'net.ipv4.ip_local_port_range',
    'net.ipv4.ip_unprivileged_port_start', 'net.ipv4.ping_group_range',
    'net.ipv4.tcp_syncookies'}
VOLUME_KEYS = {'name', 'configMap', 'csi', 'downwardAPI', 'emptyDir',
               'ephemeral', 'persistentVolumeClaim', 'projected', 'secret'}
APPARMOR_KEY = 'container.apparmor.security.beta.kubernetes.io/*'

_UNSET = object()


def pod_of(resource: dict):
    """``(rule prefix, pod metadata, pod spec)`` of a resource the chart's
    rules reach, or None."""
    kind = resource.get('kind')
    spec = resource.get('spec') or {}
    if kind == 'Pod':
        return '', resource.get('metadata') or {}, spec
    if kind in _CONTROLLERS:
        prefix, template = 'autogen-', spec.get('template') or {}
    elif kind == 'CronJob':
        prefix = 'autogen-cronjob-'
        template = ((spec.get('jobTemplate') or {}).get('spec') or {}) \
            .get('template') or {}
    else:
        return None
    return prefix, template.get('metadata') or {}, template.get('spec') or {}


def _containers(spec: dict) -> list:
    return [c for key in ('ephemeralContainers', 'initContainers',
                          'containers') for c in spec.get(key) or []]


def _dig(node, *path):
    """The value at ``path`` under ``node``, or ``_UNSET``."""
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return _UNSET
        node = node[key]
    return node


def _is_false(value) -> bool:
    return value is False or value == 'false'


def _is_true(value) -> bool:
    return value is True or value == 'true'


def _positive(value) -> bool:
    """``>0`` of a number, or of every number of a list."""
    if isinstance(value, list):
        return all(_positive(v) for v in value)
    return isinstance(value, (int, float)) and not isinstance(value, bool) \
        and value > 0


def _may(holders: list, path: tuple, good) -> bool:
    """Every holder's field is unset or ``good``."""
    return all(v is _UNSET or good(v)
               for v in (_dig(h, *path) for h in holders))


def _must(holders: list, path: tuple, good) -> bool:
    """Every holder's field is set and ``good``."""
    return all(v is not _UNSET and good(v)
               for v in (_dig(h, *path) for h in holders))


def _pod_or_every_container(spec: dict, path: tuple, good) -> bool:
    """The shape of the chart's three ``anyPattern`` rules: the Pod's own
    security context sets the field and no container contradicts it, or
    every container sets it."""
    containers = _containers(spec)
    field = ('securityContext',) + path
    return (_must([spec], field, good) and _may(containers, field, good)) \
        or _must(containers, field, good)


def _capabilities(container: dict, key: str) -> list:
    value = _dig(container, 'securityContext', 'capabilities', key)
    return value if isinstance(value, list) else []


def verdicts(meta: dict, spec: dict) -> list:
    """``[(policy, rule, passes)]`` of the chart's rules for one Pod, in
    the pack's order."""
    containers = _containers(spec)
    with_pod = [spec] + containers
    sc = ('securityContext',)
    added = [cap for c in containers for cap in _capabilities(c, 'add')]
    volumes = spec.get('volumes') or []
    sysctls = _dig(spec, *sc, 'sysctls')
    apparmor = [v for k, v in (meta.get('annotations') or {}).items()
                if fnmatch.fnmatchcase(k, APPARMOR_KEY)]
    return [
        ('disallow-capabilities', 'adding-capabilities',
         all(cap in ALLOWED_CAPABILITIES for cap in added)),
        ('disallow-host-namespaces', 'host-namespaces',
         _may([spec], ('hostPID',), _is_false)
         and _may([spec], ('hostIPC',), _is_false)
         and _may([spec], ('hostNetwork',), _is_false)),
        ('disallow-host-path', 'host-path',
         all('hostPath' not in v for v in volumes)),
        ('disallow-host-ports', 'host-ports-none',
         all(_may(c.get('ports') or [], ('hostPort',), lambda v: v == 0)
             for c in containers)),
        ('disallow-host-process', 'host-process-containers',
         _may(containers, sc + ('windowsOptions', 'hostProcess'),
              _is_false)),
        ('disallow-privileged-containers', 'privileged-containers',
         _may(containers, sc + ('privileged',), _is_false)),
        ('disallow-proc-mount', 'check-proc-mount',
         _may(containers, sc + ('procMount',), lambda v: v == 'Default')),
        ('disallow-selinux', 'selinux-type',
         _may(with_pod, sc + ('seLinuxOptions', 'type'),
              lambda v: v in SELINUX_TYPES)),
        ('disallow-selinux', 'selinux-user-role',
         all(_dig(h, *sc, 'seLinuxOptions', key) is _UNSET
             for h in with_pod for key in ('user', 'role'))),
        ('restrict-apparmor-profiles', 'app-armor',
         all(v == 'runtime/default' or
             (isinstance(v, str) and v.startswith('localhost/'))
             for v in apparmor)),
        ('restrict-seccomp', 'check-seccomp',
         _may(with_pod, sc + ('seccompProfile', 'type'),
              lambda v: v in SECCOMP_TYPES)),
        ('restrict-sysctls', 'check-sysctls',
         _may(sysctls if isinstance(sysctls, list) else [],
              ('name',), lambda v: v in SAFE_SYSCTLS)),
        ('disallow-capabilities-strict', 'require-drop-all',
         all('ALL' in [str(cap).upper() for cap in _capabilities(c, 'drop')]
             for c in containers)),
        ('disallow-capabilities-strict', 'adding-capabilities-strict',
         all(cap in ('NET_BIND_SERVICE', '') for cap in added)),
        ('disallow-privilege-escalation', 'privilege-escalation',
         _must(containers, sc + ('allowPrivilegeEscalation',), _is_false)),
        ('require-run-as-non-root-user', 'run-as-non-root-user',
         _may(with_pod, sc + ('runAsUser',), _positive)),
        ('require-run-as-nonroot', 'run-as-non-root',
         _pod_or_every_container(spec, ('runAsNonRoot',), _is_true)),
        ('restrict-seccomp-strict', 'check-seccomp-strict',
         _pod_or_every_container(spec, ('seccompProfile', 'type'),
                                 lambda v: v in SECCOMP_TYPES)),
        ('restrict-volume-types', 'restricted-volumes',
         all(key in VOLUME_KEYS for v in volumes for key in v)),
        ('require-non-root-groups', 'check-runasgroup',
         _pod_or_every_container(spec, ('runAsGroup',), _positive)),
        ('require-non-root-groups', 'check-supplementalgroups',
         _may([spec], sc + ('supplementalGroups',), _positive)),
        ('require-non-root-groups', 'check-fsgroup',
         _may([spec], sc + ('fsGroup',), _positive)),
    ]


POLICIES = tuple(dict.fromkeys(p for p, _r, _v in verdicts({}, {})))


def rows(resource: dict) -> list:
    """The chart's rows for one resource, sorted as a report sorts them."""
    pod = pod_of(resource)
    if pod is None:
        return []
    prefix, meta, spec = pod
    return sorted((policy, prefix + rule, 'pass' if passes else 'fail')
                  for policy, rule, passes in verdicts(meta, spec))
