"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip sharding is validated on host CPU devices (the driver
separately dry-runs the multi-chip path via __graft_entry__.py);
chip_smoke.py and benchmarks/run.py run on the real TPU outside of pytest.
"""

import os
import sys

# Tests force the CPU whatever the machine exports: they need eight
# virtual devices for the sharded paths, and they must not take the chip
# (it belongs to one process at a time) from a run that is using it.
# The config update covers a pytest plugin having imported jax before
# this file set the variable.
os.environ['JAX_PLATFORMS'] = 'cpu'
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (flags + ' --xla_force_host_platform_device_count=8').strip()
import jax  # noqa: E402
jax.config.update('jax_platforms', 'cpu')

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        'markers',
        'slow: long-running (full crash/chaos matrices); tier-1 runs '
        "-m 'not slow'")


# ---------------------------------------------------------------------------
# slow-marker audit bookkeeping (ISSUE-7 satellite): accumulate wall time
# per test FAMILY (a parametrized function is one family) across the
# session, and record which families carry the `slow` marker. The audit
# test itself lives in tests/test_slow_audit.py and is reordered to run
# LAST, so it sees the whole session's totals — an unmarked family that
# grows past its budget fails tier-1 loudly instead of silently pushing
# the suite toward its 870s timeout.
# ---------------------------------------------------------------------------

FAMILY_DURATIONS = {}      # nodeid-without-parametrization -> seconds
SLOW_FAMILIES = set()      # families carrying the `slow` marker


def _family(nodeid):
    return nodeid.split('[', 1)[0]


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker('slow'):
            SLOW_FAMILIES.add(_family(item.nodeid))
    # the audit must observe every other test: push its module to the end
    items.sort(key=lambda item: item.module.__name__ == 'test_slow_audit'
               if hasattr(item, 'module') else False)


def pytest_runtest_logreport(report):
    if report.when in ('setup', 'call', 'teardown'):
        fam = _family(report.nodeid)
        FAMILY_DURATIONS[fam] = FAMILY_DURATIONS.get(fam, 0.0) + \
            (report.duration or 0.0)
