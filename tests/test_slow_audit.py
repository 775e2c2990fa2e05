"""Slow-marker audit (ISSUE-7 satellite): tier-1's 870s timeout is a
budget, and this test is its enforcement. conftest.py accumulates wall
time per test FAMILY (one parametrized function = one family, summed
across its whole matrix) and reorders this module to run LAST, so the
assertions below see the finished session.

The rule: a family not marked `slow` gets DEFAULT_BUDGET_S (~5s — new
tests that need more belong under `-m slow`, or must appear in the
grandfather table below with an explicit measured budget). The
grandfather budgets are the pre-existing heavy families at ~2x their
measured tier-1 cost on the reference box — headroom for box noise,
tight enough that a matrix that doubles fails loudly here instead of
silently eating the suite's timeout.

Scaled-up offline runs (CHAOS_SEEDS/FUZZ_CASES/etc.) legitimately blow
these budgets: the audit disarms itself when the scaling env knobs are
set, and entirely under AUTOMERGE_TPU_SLOW_AUDIT=0.
"""

import os

import conftest

# 5.0s is calibrated on the >=2-core reference box. A 1-core box
# (the round-21 driver) at least doubles every family's wall time —
# XLA compiles lose their thread pool and the 3-4s families straddle
# the default on scheduling noise alone (observed: 3.2s -> 5.4s run to
# run) — so the default scales up there. Grandfather budgets already
# carry contended-worst-case headroom and stay fixed.
DEFAULT_BUDGET_S = 5.0 if (os.cpu_count() or 2) >= 2 else 12.0

# family (tests/<file>.py::<function>) -> tier-1 budget in seconds,
# ~2.5x the family's measured cost on the reference box (2026-08-03
# full-run --durations sweep) so box noise passes but a doubled matrix
# fails.
GRANDFATHER_BUDGETS = {
    'tests/test_chaos.py::test_chaos_differential': 320.0,
    'tests/test_flight_recorder.py::'
    'test_recovery_rot_produces_forensic_dump': 27.0,
    'tests/test_chaos.py::test_chaos_lossy_wire': 25.0,
    'tests/test_flight_recorder.py::'
    'test_quarantine_dump_names_durable_id': 23.0,
    'tests/test_service_chaos.py::'
    'test_service_chaos_identical_across_device_modes': 15.0,
    'tests/test_sequence.py::TestLongDocSharding::'
    'test_sharded_matches_local': 15.0,
    # measured 3.9s isolated / ~4.8s in-suite on the reference box, but
    # observed at 22.2s under full-suite contention on this box (round
    # 14; family wall time UNCHANGED vs the prior tree, so contention,
    # not a regression) — budgeted off the contended worst case
    'tests/test_chaos.py::test_chaos_checkpoint_crash_recover': 30.0,
    'tests/test_multihost.py::'
    'test_two_process_pairwise_sync_converges': 12.0,
    # spawns a python child (jax import) that dies inside the vacuum's
    # manifest swap; 1.8s isolated, budgeted for suite contention
    'tests/test_storage_tier.py::TestDiskArena::'
    'test_kill_mid_vacuum_recovers': 12.0,
    'tests/test_fleet_backend.py::TestSequenceSeam::'
    'test_randomized_sequence_counter_differential': 10.0,
    # 20 cases over three (capacity, width) shapes at 4 and 8 lanes, each
    # a compile of the sequence kernel: 3.6s alone on the 8-core box
    'tests/test_sequence.py::TestStepAgainstPlainStep::'
    'test_masked_corners': 12.0,
    # 4.0s, 1.3s and 3.5s alone on the 8-core box; 6.5-6.7s, 5.2s and 6.1s
    # in six- and four-worker runs of PRs 33 and 37 on a loaded machine
    # (the family's cost unchanged in isolation, at the parent too)
    'tests/test_loader.py::TestBulkLoad::'
    'test_differential_reads_and_patches': 12.0,
    'tests/test_loader.py::TestBulkLoad::'
    'test_objects_inside_lists_bulk_load': 10.0,
    'tests/test_fleet.py::test_grid_variants_agree': 10.0,
    # 2.9s alone on the 8-core box; 5.9s in a six-worker run of PR 37 on a
    # loaded machine (exact_device renumbering: no code of that PR's)
    'tests/test_fleet_backend.py::TestExactDeviceMode::'
    'test_renumber_beyond_slot_capacity_grows_first': 10.0,
    # PR 37: each compiles the sequence (or register) programs of its own
    # shapes in its first case; 3.6s each alone on the 8-core box
    'tests/test_seq_heldback.py::'
    'test_a_withheld_change_arrives_a_call_later': 10.0,
    'tests/test_seq_heldback.py::'
    'test_sixteen_documents_five_of_them_holding_back': 10.0,
    'tests/test_seq_heldback.py::'
    'test_the_register_engine_gets_its_rows_in_applied_order': 10.0,
    'tests/test_service_chaos.py::'
    'test_service_overload_brownout_smoke': 10.0,
    'tests/test_service_chaos.py::test_service_chaos_smoke': 10.0,
    # 4.3s isolated; observed 25.8s under full-suite I/O contention on
    # this 9p box (round 19 — the suite's file-heavy families draw a
    # latency lottery; family cost unchanged in isolation)
    'tests/test_durability.py::test_crashtest_smoke': 40.0,
    'tests/test_durability.py::'
    'test_recovery_rejournals_instead_of_resnapshotting': 25.0,
    # 4.1s alone at ISSUE-30's parent commit and 4.8s with its sequence
    # kernel (its corpus loads Text documents, so it compiles the kernel);
    # 10.8s in two six-worker runs of this 8-core box
    'tests/test_fuzz_wire.py::test_fuzz_wire_smoke': 16.0,
    # measured 4.2-5.3s across two full runs on the 1-core round-21 box
    # (straddling the 5.0s default by box noise alone; family cost
    # unchanged in isolation) — budgeted off the contended worst case
    'tests/test_hashindex.py::TestHashIndexCore::'
    'test_host_and_device_modes_answer_identically': 12.0,
    # three engines, each compiling its own sync-round programs: 6.8s
    # alone on the 8-core box (4.1 + 1.9 + 0.9), 7.2s in a six-worker run;
    # over the default whenever the audit's worker is the one that ran it
    # (PR 31's smaller suite dealt the files to the workers anew)
    'tests/test_sync_fabric.py::TestFusedByteIdentity::'
    'test_multi_peer_rounds_with_mid_round_disconnect': 16.0,
    # ISSUE-19 sanitizer smoke: the replay parent subprocess imports the
    # full stack (jax) to build the fuzz corpus before the jax-free
    # child replays it under the cached ASan .so — 5.0s isolated,
    # budgeted for suite contention like the other child-spawners
    'tests/test_native_sanitize.py::'
    'test_sanitize_smoke_replay_under_cached_so': 20.0,
    # ISSUE-19 tier-1 contract gate: one archlint subprocess over the
    # real tree (stdlib-only AST pass, ~1.1s isolated; subprocess
    # startup draws the same contention lottery as the others)
    'tests/test_archlint.py::'
    'test_real_tree_is_clean_under_checked_in_baseline': 12.0,
    # ISSUE-13 perf-observatory family: the atomic-counter hammer (6
    # threads x 10k locked incs, measured ~2s isolated) and the torn-
    # read `_sum` exposition hammer (writer thread + 50 scrapes,
    # measured ~2.2s) — budgeted at ~4x for full-suite contention on
    # this 2-core box
    'tests/test_perf_obs.py::TestAtomicCounters::'
    'test_inc_exact_under_hammer': 10.0,
    'tests/test_export.py::test_sum_consistent_under_concurrent_'
    'recording': 10.0,
    # measured 0.35s isolated (0.22s at the prior tree — the family's
    # cost is unchanged) but observed at 10.3s under full-suite
    # contention on this box (round-17 run) — the same contention
    # class as test_chaos_checkpoint_crash_recover above; budgeted off
    # the contended worst case
    'tests/test_service.py::test_brownout_widen_fsync_and_restore': 15.0,
    # measured 9.3s and 10.5s in two full tier-1 runs of the seed on the
    # 8-core round-23 box (family unchanged since round 22, where a
    # 2-core box ran it under 5s): four shard threads under one GIL,
    # paced by wall-clock ticks — budgeted off the observed worst case
    'tests/test_control.py::'
    'test_kill_one_of_four_settles_under_active_control': 25.0,
    # ISSUE-21 bring-up gates: each test runs chip_smoke.py or a jit
    # probe as a CHILD (a fresh jax import per child, ~2s). The
    # rehearsal runs all four legs (11.9s isolated with a fifth); the cache-default
    # test runs two children (4.2s); the rest one child each (2-3s).
    # Budgeted ~3-4x for suite contention like the other child-spawners
    'tests/test_bring_up.py::test_smoke_rehearsal_runs_every_leg': 45.0,
    'tests/test_bring_up.py::'
    'test_compile_cache_defaults_to_one_fixed_path_in_the_checkout': 20.0,
    'tests/test_bring_up.py::test_compile_cache_placed_from_outside': 12.0,
    'tests/test_bring_up.py::'
    'test_smoke_refuses_cpu_without_the_rehearsal_flag': 12.0,
    'tests/test_bring_up.py::'
    'test_smoke_fails_without_the_native_codec': 12.0,
    'tests/test_bring_up.py::'
    'test_smoke_fails_when_a_leg_disagrees_with_the_oracle': 12.0,
    'tests/test_bring_up.py::test_smoke_subset_never_reports_ok': 12.0,
    # ISSUE-30: ten cases, each one dispatch and the same batch split in
    # two, at widths 1, 16, 64 and on rows of 8 and 64 slots: four shapes
    # of the sequence kernel compiled plus two of materialize (6.0s alone,
    # 5.2s after the file's other tests, 17.5s in a six-worker run before
    # the cases shared their widths)
    'tests/test_sequence.py::TestDeferredSplice::'
    'test_batch_on_its_own_nodes': 20.0,
    # 8.5s alone at ISSUE-30's parent commit and 9.7s with its kernel (the
    # exact leg compiles the sequence kernel, a quarter slower to compile
    # on the CPU since), 11.6s in a six-worker run: over the default
    # wherever the audit's worker happened to run this file too
    'tests/test_query_chaos.py::test_subscription_chaos_universe': 25.0,
}


def _audit_disarmed():
    if os.environ.get('AUTOMERGE_TPU_SLOW_AUDIT', '1') == '0':
        return True
    # offline scale knobs change the dose; budgets only hold for tier-1
    for knob in ('CHAOS_SEEDS', 'CHAOS_STEPS', 'FUZZ_CASES',
                 'CRASHTEST_CASES', 'N_WIRE_SEEDS'):
        if os.environ.get(knob):
            return True
    return False


def test_unmarked_families_fit_their_budgets():
    if _audit_disarmed():
        return
    over = []
    for family, seconds in sorted(conftest.FAMILY_DURATIONS.items()):
        if family in conftest.SLOW_FAMILIES:
            continue
        if family.endswith('test_unmarked_families_fit_their_budgets'):
            continue
        budget = GRANDFATHER_BUDGETS.get(family, DEFAULT_BUDGET_S)
        if seconds > budget:
            over.append(f'{family}: {seconds:.1f}s > {budget:.1f}s')
    assert not over, (
        'unmarked test families exceeded their tier-1 budgets — mark '
        'them `slow`, shrink the tier-1 dose, or (for a deliberate '
        'cost) add a measured budget to GRANDFATHER_BUDGETS:\n  '
        + '\n  '.join(over))
