"""Packaged-artifact smoke test — the reference re-runs its suite against
the webpack bundle (TEST_DIST=1, ref .github/workflows/automerge-ci.yml:24-31
and the src-vs-dist header of every test file, test/test.js:2). The Python
analogue: the library must work imported from a zip archive, where the C++
codec cannot build next to its source — so this doubles as the graceful-
degradation test for native.available() == False (pure-Python codecs,
host-mirror fleet paths)."""

import glob
import os
import re
import subprocess
import sys
import zipfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCENARIO = r"""
import sys
zip_path, = sys.argv[1:]
sys.path.insert(0, zip_path)
import automerge_tpu as am
from automerge_tpu import native
assert __import__('automerge_tpu').__file__.startswith(zip_path), \
    'loaded from the wrong place'
assert not native.available(), 'zip import must not see a native codec'

# end-to-end: concurrent edits, merge convergence, save/load, sync round
d1 = am.init('aa' * 4)
d1 = am.change(d1, lambda d: d.update(
    {'rows': [{'n': 1}], 't': am.Text('hi'), 'c': am.Counter(2)}))
d2 = am.merge(am.init('bb' * 4), d1)
d1 = am.change(d1, lambda d: d['c'].increment(3))
d2 = am.change(d2, lambda d: d['rows'][0].update({'n': 9}))
m1, m2 = am.merge(am.clone(d1), d2), am.merge(am.clone(d2), d1)
assert int(m1['c']) == int(m2['c']) == 5
assert m1['rows'][0]['n'] == m2['rows'][0]['n'] == 9
loaded = am.load(am.save(m1))
assert str(loaded['t']) == 'hi'

s1, s2 = am.init_sync_state(), am.init_sync_state()
peer = am.init('cc' * 4)
for _ in range(10):
    s1, msg = am.generate_sync_message(m1, s1)
    if msg is not None:
        peer, s2, _ = am.receive_sync_message(peer, s2, msg)
    s2, msg2 = am.generate_sync_message(peer, s2)
    if msg2 is not None:
        m1, s1, _ = am.receive_sync_message(m1, s1, msg2)
    if msg is None and msg2 is None:
        break
assert peer['rows'][0]['n'] == 9
print('ZIP-PACKAGED OK')
"""


def test_runs_from_zip_without_native_codec(tmp_path):
    zip_path = str(tmp_path / 'automerge_tpu.zip')
    pkg = os.path.join(ROOT, 'automerge_tpu')
    with zipfile.ZipFile(zip_path, 'w') as zf:
        for dirpath, _dirs, files in os.walk(pkg):
            for name in files:
                if name.endswith(('.py', '.cpp')):
                    full = os.path.join(dirpath, name)
                    zf.write(full, os.path.relpath(full, ROOT))
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('PYTHONPATH', None)
    scenario = str(tmp_path / 'scenario.py')
    with open(scenario, 'w') as f:
        f.write(_SCENARIO)
    proc = subprocess.run(
        [sys.executable, scenario, zip_path],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr
    assert 'ZIP-PACKAGED OK' in proc.stdout


# ---- the documents that describe the tree as it is --------------------------

_TREE_PREFIXES = ('automerge_tpu/', 'tests/', 'tools/', 'benchmarks/')
_ROOT_NAME = re.compile(r'[\w.\-*]+\.(py|json|jsonl|md)')
# `path.py:120`, `path.py:120-140`, `path.py::TestClass::test_name`
_SUFFIX = re.compile(r'(::.*|:\d+([-–]\d+)?)$')


def _named_paths(text):
    """The back-ticked tokens of a document that name a place in the
    tree: those that start with one of the tree's directories (the first
    word of the token is the path) and bare `*.py` / `*.json` / `*.jsonl`
    / `*.md` names, which are the root's."""
    for token in re.findall(r'`([^`\n]+)`', text):
        if token.startswith(_TREE_PREFIXES):
            path = token.split()[0]
        elif _ROOT_NAME.fullmatch(token):
            path = token
        else:
            continue
        yield _SUFFIX.sub('', path.rstrip('.,;:)'))


@pytest.mark.parametrize('doc', ['README.md', 'PARITY.md',
                                 '.claude/skills/verify/SKILL.md'])
def test_docs_name_only_files_that_exist(doc):
    """README.md, PARITY.md and the verify skill say what the tree holds
    today, so every file they name is there (a `*` matches as a glob).
    The records (PERF.md, ROADMAP.md, CHANGES.md, BASELINE.md) may name
    what was removed and are not read here."""
    with open(os.path.join(ROOT, doc)) as f:
        named = sorted(set(_named_paths(f.read())))
    assert named, f'{doc} names no file at all'
    missing = [path for path in named
               if not glob.glob(os.path.join(ROOT, path))]
    assert not missing, f'{doc} names files that do not exist: {missing}'
