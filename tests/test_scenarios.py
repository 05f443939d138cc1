"""Replay of the reference YAML scenario corpus (test/scenarios) through
the engine (reference: pkg/testrunner/scenario.go:30-50 +
testrunner_test.go's enabled list), consumed in place from the read-only
reference checkout."""

import os

import pytest

from kyverno_tpu.conformance.scenarios import REF_ROOT, run_scenario

if not os.path.isdir(REF_ROOT):
    pytest.skip(f'the reference checkout {REF_ROOT} is not on this machine',
                allow_module_level=True)

#: the reference's own enabled scenario list
#: (pkg/testrunner/testrunner_test.go)
SCENARIOS = [
    'test/scenarios/other/scenario_mutate_endpoint.yaml',
    'test/scenarios/other/scenario_mutate_validate_qos.yaml',
    'test/scenarios/samples/best_practices/disallow_priviledged.yaml',
    'test/scenarios/other/scenario_validate_healthChecks.yaml',
    'test/scenarios/samples/best_practices/disallow_host_network_port.yaml',
    'test/scenarios/samples/best_practices/disallow_host_pid_ipc.yaml',
    'test/scenarios/other/'
    'scenario_validate_disallow_default_serviceaccount.yaml',
    'test/scenarios/other/scenario_validate_selinux_context.yaml',
    'test/scenarios/other/scenario_validate_default_proc_mount.yaml',
    'test/scenarios/other/scenario_validate_volume_whiltelist.yaml',
    'test/scenarios/samples/best_practices/disallow_bind_mounts_fail.yaml',
    'test/scenarios/samples/best_practices/disallow_bind_mounts_pass.yaml',
    'test/scenarios/samples/best_practices/add_safe_to_evict.yaml',
    'test/scenarios/samples/best_practices/add_safe_to_evict2.yaml',
    'test/scenarios/samples/best_practices/add_safe_to_evict3.yaml',
    'test/scenarios/samples/more/restrict_automount_sa_token.yaml',
    'test/scenarios/samples/more/restrict_ingress_classes.yaml',
    'test/scenarios/samples/more/unknown_ingress_class.yaml',
    # additional corpus files beyond the reference's enabled list
    'test/scenarios/other/scenario_mutate_pod_spec.yaml',
    'test/scenarios/samples/best_practices/add_networkPolicy.yaml',
    'test/scenarios/samples/best_practices/add_ns_quota.yaml',
]


def _exists(rel):
    return os.path.isfile(os.path.join(REF_ROOT, rel))


def test_scenario_paths_exist():
    missing = [s for s in SCENARIOS if not _exists(s)]
    assert not missing, f'scenario corpus drifted: {missing}'


@pytest.mark.parametrize('rel', SCENARIOS)
def test_scenario(rel):
    assert run_scenario(rel) >= 1
