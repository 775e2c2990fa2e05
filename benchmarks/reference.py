"""The plain reference of the map configurations, independent of the code
under test: it imports nothing of ``automerge_tpu``.

Every change the benchmark's generators make is a single ``set`` of an int
on a root key with ``pred: []``: no op overwrites another, so all sets of a
key stay as concurrent values and the document shows the one with the
greatest opId, compared Lamport-wise by (counter, actor) — automerge-classic
``backend/new.js`` ``lamportCompare``. ``map_view`` folds that rule over the
ops the generator itself recorded. ``saved_document_differs`` reads a saved
document back with the benchmark's own reader of the binary format
(wire.py) and holds it against the same record.
"""

from wire import read_document


def map_view(ops):
    """{key: value} a map document shows after `ops`, an iterable of
    (counter, actor, key, value); order does not matter."""
    best = {}
    for counter, actor, key, value in ops:
        seen = best.get(key)
        if seen is None or (counter, actor) > seen[0]:
            best[key] = ((counter, actor), value)
    return {key: value for key, (_opid, value) in best.items()}


def saved_document_differs(data, ops, heads):
    """None where the saved document `data` holds exactly the log it was
    given, else a line that says what differs. The log is `ops`, one
    one-op change each as (counter, actor, key, value), every actor's
    changes following its own last one, and `heads`, the hashes of each
    actor's last change. Held to it: the checksum, the heads, every
    change's actor, sequence number, greatest opId and dependencies, and
    every op's key, opId, value and lack of successors, in the document's
    order (by key, then Lamport)."""
    try:
        doc = read_document(data)
    except (ValueError, IndexError, TypeError) as exc:
        return f'does not read back: {exc}'
    if doc['heads'] != sorted(heads):
        return f"heads {doc['heads']}, recorded {sorted(heads)}"
    want_ops = sorted((key, counter, actor, value, 0)
                      for counter, actor, key, value in ops)
    if doc['ops'] != want_ops:
        return (f"{len(doc['ops'])} ops, {len(want_ops)} recorded, "
                f"{len(set(doc['ops']) & set(want_ops))} in both"
                + (', out of order' if sorted(doc['ops']) == want_ops
                   else ''))
    want_changes = {(actor, counter, counter,
                     frozenset({(actor, counter - 1)} if counter > 1
                               else ()))
                    for counter, actor, _key, _value in ops}
    got_changes = [(actor, seq, max_op, frozenset(deps))
                   for actor, seq, max_op, deps in doc['changes']]
    if len(got_changes) != len(want_changes) or \
            set(got_changes) != want_changes:
        return (f'{len(got_changes)} changes, {len(want_changes)} '
                f'recorded, {len(set(got_changes) & want_changes)} in both')
    return None
