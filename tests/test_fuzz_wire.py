"""Tier-1 smoke dose of the wire fuzzer (tools/fuzz_wire.py): hostile
bytes into every decode entry point must raise only TYPED errors
(AutomergeError subclasses) — no bare IndexError/KeyError/AssertionError,
no hang — and batched entry points must never let a poisoned input
perturb a healthy neighbour. CHAOS-style env scaling: FUZZ_SEEDS /
FUZZ_CASES raise the dose for offline runs (tools/fuzz_wire.py standalone
defaults to ~10x this smoke dose)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), 'tools'))

from fuzz_wire import build_corpus, mutate, run_fuzz   # noqa: E402

N_SEEDS = int(os.environ.get('FUZZ_SEEDS', '2'))
N_CASES = int(os.environ.get('FUZZ_CASES', '20'))


def test_fuzz_wire_smoke():
    stats = run_fuzz(n_seeds=N_SEEDS, n_cases=N_CASES)
    assert stats['escaped'] == [], \
        f"untyped errors escaped the decoders: {stats['escaped'][:10]}"
    # the dose genuinely exercised hostile inputs, not just clean echoes
    assert stats['rejected'] > 0
    assert stats['cases'] > N_SEEDS * N_CASES


def test_fuzz_corpus_registered():
    """The corpus size lands in the health roll-up so CI can see
    the fuzz surface."""
    from automerge_tpu.observability import health_counts
    build_corpus()
    counts = health_counts()
    assert counts.get('fuzz_corpus_size', 0) > 0


def test_durability_decoders_in_fuzz_surface():
    """The journal/snapshot/manifest frame decoders are first-class fuzz
    targets with corpus entries of their own (hostile DISK bytes get the
    same typed envelope as hostile wire bytes)."""
    from fuzz_wire import _targets
    corpus = build_corpus()
    assert {'journal', 'snapshot', 'manifest'} <= set(corpus)
    names = {name for name, _fn in _targets()}
    assert {'journal_strict', 'journal_lenient', 'snapshot_frames',
            'manifest'} <= names
    # the lenient scan consumes arbitrary garbage without raising
    import random
    from automerge_tpu.fleet.durability import parse_journal_bytes
    rng = random.Random(3)
    for _ in range(20):
        blob = mutate(rng, corpus['journal'][0])
        records, info = parse_journal_bytes(blob)
        assert isinstance(records, list)


def test_native_column_count_bombs_are_typed():
    """Regression (found by the widened fuzz corpus): RLE/boolean run
    counts are attacker-controlled expansion factors. A boolean run
    near 2^64 used to overflow the int64 capacity check in
    codec.cpp:am_decode_boolean and smash the heap (SIGSEGV); an RLE
    column can declare 2^40+ values in a dozen bytes and turn the
    caller's allocation into a DoS. Both must be TYPED rejections."""
    from automerge_tpu import native
    from automerge_tpu.errors import WireCorruption
    if not native.available():
        pytest.skip('native codec unavailable')

    huge_uleb = b'\xff' * 9 + b'\x01'          # run count with bit 63 set
    with pytest.raises(WireCorruption):
        native.decode_boolean_column(huge_uleb)

    def leb(v):
        out = bytearray()
        while True:
            byte = v & 0x7f
            v >>= 7
            if v:
                out.append(byte | 0x80)
            else:
                out.append(byte)
                return bytes(out)

    bomb = leb(1 << 40) + leb(7)               # "2^40 copies of 7"
    with pytest.raises(WireCorruption):
        native.decode_rle_column(bomb)
    with pytest.raises(WireCorruption):
        native.decode_delta_column(bomb)


def test_mutator_determinism():
    """Same seed, same mutants — the fuzz trace must be reproducible."""
    import random
    corpus = build_corpus()
    base = corpus['change'][0]
    a = [mutate(random.Random(7), base) for _ in range(5)]
    b = [mutate(random.Random(7), base) for _ in range(5)]
    # each Random(7) instance replays the identical draw sequence
    assert a[0] == b[0]
