"""Faults planted underneath the timed path, for the control: each breaks
a guarantee the configurations state (an acknowledged change is in the
document), and each has to make ``correct`` read false. Used by the tests
(tiny sizes, CPU) and by control.py (the cell's own size, on the chip); the
benchmark's own runs never plant one."""

FAULTS = ('state_unchanged', 'half_the_batch', 'one_answer')


def plant(fault):
    """Replace the seam's entry (`fleet.backend.apply_changes_docs`, which
    the bulk step calls) with one that:

    state_unchanged  returns its handles as they came, nothing applied
    half_the_batch   leaves every second document's changes out
    one_answer       drops the last change of the call's last document

    and reports success all the same. Returns a function that undoes it."""
    from automerge_tpu.fleet import backend as fleet_backend
    if fault not in FAULTS:
        raise ValueError(f'unknown fault {fault!r}; one of {FAULTS}')
    real = fleet_backend.apply_changes_docs

    def broken(handles, per_doc, *args, **kwargs):
        if fault == 'state_unchanged':
            return list(handles), [None] * len(handles)
        per_doc = [list(changes) for changes in per_doc]
        if fault == 'half_the_batch':
            for d in range(0, len(per_doc), 2):
                per_doc[d] = []
        else:
            per_doc[-1] = per_doc[-1][:-1]
        return real(handles, per_doc, *args, **kwargs)

    fleet_backend.apply_changes_docs = broken

    def undo():
        fleet_backend.apply_changes_docs = real
    return undo
