"""The turbo seam lays the grid's op columns out a document run at a time
(ISSUE-40: `ingest.doc_runs` / `ingest.layout_doc_runs`). Held bit for bit
to a two-dimensional scatter `arr[rows, pos] = col` written out here: on
the helper, on the whole OpBatch a turbo call hands the grid kernel (a row
a document of the call, beside each row's slot), and end to end against
the exact path."""

import numpy as np
import pytest

from automerge_tpu import native
from automerge_tpu.columnar import decode_change, encode_change
from automerge_tpu.fleet import backend as fleet_backend
from automerge_tpu.fleet.backend import DocFleet
from automerge_tpu.fleet.ingest import doc_runs, layout_doc_runs
from automerge_tpu.fleet.tensor_doc import OpBatch

A, B = 'aa' * 16, 'bb' * 16


def scatter_reference(slots, n_docs, cols, dtypes, width=None):
    """The parent's layout: each row's lane is its rank within its run;
    `width` lanes a row, the longest run's where not given."""
    n = len(slots)
    starts = np.r_[0, np.flatnonzero(slots[1:] != slots[:-1]) + 1] \
        if n else np.zeros(0, dtype=np.int64)
    lens = np.diff(np.r_[starts, n])
    pos = np.arange(n) - np.repeat(starts, lens)
    max_ops = max(int(lens.max()) if n else 0, 1)
    out = []
    for col, dt in zip(cols, dtypes):
        arr = np.zeros((n_docs, width or max_ops), dtype=dt)
        arr[slots, pos] = col
        out.append(arr)
    return out


def run_layout(slots, n_docs, cols, dtypes):
    starts, lens = doc_runs(slots)
    max_ops = max(int(lens.max()) if len(lens) else 0, 1)
    return layout_doc_runs(slots[starts], lens, max_ops, n_docs, cols,
                           dtypes)


def same_arrays(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


@pytest.mark.parametrize('run_slots,run_lens,n_docs', [
    pytest.param([3, 0, 2, 1], [2, 2, 2, 2], 4, id='slots-out-of-order'),
    pytest.param([0, 1, 2], [3, 1, 2], 3, id='ragged'),
    pytest.param([2, 0, 3, 1], [1, 4, 2, 4], 4, id='ragged-out-of-order'),
    pytest.param([0, 1, 2, 3, 4], [4] * 5, 5, id='every-run-full'),
    pytest.param([2, 0, 1], [1, 1, 1], 3, id='max-ops-1'),
    pytest.param([0, 1], [2, 3], 8, id='n-cap-above-docs-used'),
    pytest.param([0, 2], [2, 1], 3, id='doc-without-rows-between'),
    pytest.param([], [], 4, id='no-rows'),
])
def test_layout_doc_runs_is_the_scatter(run_slots, run_lens, n_docs):
    rng = np.random.default_rng(sum(run_lens) + n_docs)
    slots = np.repeat(np.asarray(run_slots, dtype=np.int32),
                      np.asarray(run_lens, dtype=np.int64))
    n = len(slots)
    cols = (rng.integers(-2**40, 2**40, n),                  # cast down
            rng.integers(-2**31, 2**31, n).astype(np.int32),
            rng.integers(0, 3, n).astype(np.uint8))
    dtypes = (np.int32, np.int32, np.uint8)
    same_arrays(run_layout(slots, n_docs, cols, dtypes),
                scatter_reference(slots, n_docs, cols, dtypes))


# ---------------------------------------------------------------------------
# the whole turbo call
# ---------------------------------------------------------------------------

def change(actor, seq, start_op, ops, deps=()):
    buf = encode_change({'actor': actor, 'seq': seq, 'startOp': start_op,
                         'time': 0, 'message': '', 'deps': sorted(deps),
                         'ops': ops})
    return buf, decode_change(buf)['hash']


def set_op(key, value, pred=(), datatype='int'):
    return {'action': 'set', 'obj': '_root', 'key': key, 'value': value,
            'datatype': datatype, 'pred': list(pred)}


def sets_log(doc, shape):
    """One actor's chain: a change a count in `shape`, each of that many
    sets on keys of their own."""
    log, head, op = [], [], 1
    for seq, n_ops in enumerate(shape, 1):
        buf, digest = change(A, seq, op, [
            set_op(f'k{op + i}', 100 * doc + op + i) for i in range(n_ops)],
            head)
        log.append(buf)
        head, op = [digest], op + n_ops
    return log


def grid_case(shapes, order, doc_capacity=8):
    """Documents that get `shapes[d]` (no changes where None), their
    handles passed in `order` of slots, in one call."""
    def calls():
        return [[sets_log(d, shape) if shape is not None else []
                 for d, shape in enumerate(shapes)]]
    return calls, order, doc_capacity


def counters_and_deletes():
    """A first call of sets and counters, then one of deletes and incs on
    both actors' documents: the call whose kill lanes and inc attribution
    read the root rows' pred offsets."""
    first, second = [], []
    for d in range(3):
        actor = (A, B)[d % 2]
        c1, h1 = change(actor, 1, 1, [
            set_op('x', d), set_op('y', d + 1),
            set_op('n', 10 * d, datatype='counter')])
        c2, _ = change(actor, 2, 4, [
            {'action': 'del', 'obj': '_root', 'key': 'x',
             'pred': [f'1@{actor}']},
            {'action': 'inc', 'obj': '_root', 'key': 'n', 'value': d + 2,
             'datatype': 'counter', 'pred': [f'3@{actor}']},
            set_op('z', d)], [h1])
        first.append([c1])
        second.append([c2] if d != 1 else [])
    return [first, second]


CASES = {
    'slots-out-of-order-ragged': grid_case([[2, 1], [1], [3, 3], [1, 1, 1]],
                                           [3, 1, 0, 2]),
    'every-run-full': grid_case([[2, 2], [4], [1, 3]], [2, 0, 1]),
    'max-ops-1': grid_case([[1], [1], [1]], [1, 2, 0]),
    'n-cap-above-docs-used': grid_case([[1, 2], [3]], [1, 0],
                                       doc_capacity=32),
    'doc-without-rows-between': grid_case([[2], None, [1, 1, 1]],
                                          [2, 1, 0]),
    'deletes-and-incs': (counters_and_deletes, [2, 0, 1], 8),
}


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
@pytest.mark.parametrize('case', list(CASES))
def test_the_turbo_batch_is_the_scatter_and_the_exact_path(case,
                                                           monkeypatch):
    make_calls, order, doc_capacity = CASES[case]
    calls = make_calls()
    seen = {}

    def spy(name):
        real = getattr(fleet_backend, name)

        def wrapped(*args):
            seen.setdefault(name, []).append(args)
            return real(*args)
        monkeypatch.setattr(fleet_backend, name, wrapped)

    spy('doc_runs')
    spy('layout_doc_runs')
    turbo_fleet = DocFleet(doc_capacity=doc_capacity, key_capacity=8)
    batches = []
    real_dispatch = turbo_fleet._dispatch_grid

    def dispatch(batch, kills=None, rows=None):
        batches.append((batch, kills, rows))
        return real_dispatch(batch, kills, rows)
    turbo_fleet._dispatch_grid = dispatch
    inc_preds = []
    real_note = turbo_fleet._note_grid_batch

    def note(*args):
        inc_preds.append(np.asarray(args[5]))
        return real_note(*args)
    turbo_fleet._note_grid_batch = note
    exact_fleet = DocFleet(doc_capacity=doc_capacity, key_capacity=8)
    n = len(calls[0])
    turbo = fleet_backend.init_docs(n, turbo_fleet)
    exact = fleet_backend.init_docs(n, exact_fleet)
    turbo = [turbo[i] for i in order]
    exact = [exact[i] for i in order]
    for per_doc in calls:
        per_doc = [per_doc[i] for i in order]
        turbo, _ = fleet_backend.apply_changes_docs(turbo, per_doc,
                                                    mirror=False)
        exact, _ = fleet_backend.apply_changes_docs(exact, per_doc)
    assert turbo_fleet.metrics.turbo_calls == len(calls)
    assert turbo_fleet.metrics.fallbacks == 0

    # each grid batch, against the scatter of the same rows: a row a
    # document of the call, in the order of their runs, as many rows as the
    # power of two that holds them (each row's slot beside the batch), the
    # longest run's width, its power of two where runs differ
    assert len(batches) == len(calls) == len(seen['doc_runs'])
    slot_of = np.array([h['state']._impl.slot for h in turbo])
    for (batch, kills, rows), (doc_arr,), layout_args in zip(
            batches, seen['doc_runs'], seen['layout_doc_runs']):
        cols, dtypes = layout_args[4:]
        starts, lens = doc_runs(doc_arr)
        width = int(lens.max())
        if (lens < width).any():
            width = 1 << (width - 1).bit_length()
        n_rows = 1 << (len(lens) - 1).bit_length()
        key_id, packed, value, flags = scatter_reference(
            np.repeat(np.arange(len(lens), dtype=np.int32), lens), n_rows,
            cols, dtypes, width)
        want = OpBatch(key_id, packed, value, flags == 1, flags == 2,
                       flags != 0)
        same_arrays(batch.tree_flatten()[0], want.tree_flatten()[0])
        same_arrays([rows], [np.r_[slot_of[doc_arr[starts]],
                                   np.zeros(n_rows - len(lens))]
                             .astype(np.int32)])
    # the call with deletes and incs still reads its pred offsets: kill
    # lanes for the deletes, each inc's pred (op 3 of its document's
    # actor) for the winner mirror's check
    deletes = case == 'deletes-and-incs'
    assert [kills is not None for _, kills, _rows in batches] == \
        [False] * (len(calls) - 1) + [deletes]
    assert [len(p) for p in inc_preds] == [0] * (len(calls) - 1) + \
        [2 * deletes]
    assert (inc_preds[-1] >> 8 == 3).all()

    # and end to end, the exact path's documents
    assert fleet_backend.materialize_docs(turbo) == \
        fleet_backend.materialize_docs(exact)
    for t, e in zip(turbo, exact):
        assert bytes(fleet_backend.save(t)) == bytes(fleet_backend.save(e))
    if case == 'deletes-and-incs':
        got = fleet_backend.materialize_docs(turbo)
        assert [doc.get('x') for doc in got] == [None, None, 1]
        assert [doc['n'] for doc in got] == [10 * i + (i + 2) * (i != 1)
                                             for i in order]


def with_a_string():
    """counters_and_deletes with one more document, whose value is a
    string: a flush over it takes the Python decode (_flush_mixed)."""
    calls = counters_and_deletes()
    calls[0].append([change(A, 1, 1, [set_op('s', 'text', datatype=None)])[0]])
    calls[1].append([])
    return calls


@pytest.mark.skipif(not native.available(), reason='needs the native codec')
@pytest.mark.parametrize('sharded', [False, True], ids=['one-device',
                                                        'mesh'])
@pytest.mark.parametrize('path,make_calls', [
    ('turbo', counters_and_deletes), ('flush', counters_and_deletes),
    ('flush-mixed', with_a_string)])
def test_every_grid_dispatch_takes_a_row_a_slot(path, make_calls, sharded):
    """Each caller of the grid dispatch hands it the rows of the slots its
    call touched (a row a slot, `rows` beside the batch, as many rows as
    the power of two that holds them), and a sharded fleet's grid ends as
    an unsharded one's: the batch is spread to its capacity there."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ('docs',)) if sharded else None
    fleet = DocFleet(doc_capacity=8, key_capacity=8, mesh=mesh)
    plain = DocFleet(doc_capacity=8, key_capacity=8)
    calls = make_calls()
    rows_seen = []
    real_dispatch = fleet._dispatch_grid

    def dispatch(batch, kills, rows):
        rows_seen.append(rows)
        return real_dispatch(batch, kills, rows)
    fleet._dispatch_grid = dispatch
    handles = fleet_backend.init_docs(len(calls[0]), fleet)
    reference = fleet_backend.init_docs(len(calls[0]), plain)
    for per_doc in calls:
        handles, _ = fleet_backend.apply_changes_docs(
            handles, per_doc, mirror=path != 'turbo')
        reference, _ = fleet_backend.apply_changes_docs(reference, per_doc,
                                                        mirror=False)
        if path != 'turbo':
            fleet.flush()
        touched = [h['state']._impl.slot for h, p in zip(handles, per_doc)
                   if p]
        rows = rows_seen[-1]
        assert len(rows) == 1 << (len(touched) - 1).bit_length()
        assert sorted(rows[:len(touched)].tolist()) == sorted(touched)
    assert fleet.metrics.turbo_calls == len(calls) * (path == 'turbo')
    assert len(rows_seen) == len(calls)
    for got, want in zip(fleet.state.tree_flatten()[0],
                         plain.state.tree_flatten()[0]):
        assert np.array_equal(np.asarray(got)[:fleet.n_slots],
                              np.asarray(want)[:plain.n_slots])
    assert fleet_backend.materialize_docs(handles) == \
        fleet_backend.materialize_docs(reference)
