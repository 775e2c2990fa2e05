"""The plain reference of the map store (map-store-ycsb), independent of the
code under test: it imports nothing of ``automerge_tpu``.

A record is a dict of its fields. Every update the generator makes sets one
field and names the field's last op as its predecessor, and a record's
updates form one causal chain, so a field shows the value of its last
update, in the order the generator made them, or the load's where none
came. ``Reference`` holds the load as one array of bytes (records x fields
x field_bytes) and the updates applied since as a dict of records;
``record(r)`` is record r as a read returns it: every field, as a string.
``saved_record_differs`` reads a saved record back with the benchmark's
own reader (wire_ycsb.py) and holds it against the history recorded.
"""

from wire_ycsb import read_record


class Reference:

    def __init__(self, load_values, fields, field_bytes):
        self.load_values = load_values     # uint8 [records, fields * bytes]
        self.names = [f'field{i}' for i in range(fields)]
        self.field_bytes = field_bytes
        self.updated = {}                  # record -> {field: value}
        self._cache = {}                   # record -> record(r) as it stands

    def record(self, r):
        """Record r: {field: string} for every field."""
        out = self._cache.get(r)
        if out is None:
            row = self.load_values[r].tobytes().decode()
            size = self.field_bytes
            out = {name: row[i * size:(i + 1) * size]
                   for i, name in enumerate(self.names)}
            out.update(self.updated.get(r, ()))
            self._cache[r] = out
        return out

    def update(self, records, fields, values):
        """Apply updates in order: record records[i] sets field fields[i]
        to values[i] (bytes)."""
        for r, f, value in zip(records, fields, values):
            self.updated.setdefault(r, {})[self.names[f]] = value.decode()
            self._cache.pop(r, None)


def saved_record_differs(data, history):
    """None where the saved record `data` holds exactly its recorded
    history, else a line that says what differs. `history` is
    {'heads': [hex], 'changes': [(actor, seq, max_op, {(actor, seq)})],
    'ops': [(key, counter, actor, value)]} of every op applied, the load's
    and the updates'. Held to it: the checksum, the head, every change's
    actor, sequence number, greatest opId and dependency, and every op's
    key, opId, value and successors: an op has one where a later op of
    the history sets its field, none otherwise."""
    try:
        doc = read_record(data)
    except (ValueError, IndexError, TypeError) as exc:
        return f'does not read back: {exc}'
    if doc['heads'] != history['heads']:
        return f"heads {doc['heads']}, recorded {history['heads']}"
    last = {}
    for key, counter, _actor, _value in history['ops']:
        last[key] = max(last.get(key, 0), counter)
    want_ops = sorted((key, counter, actor, value,
                       0 if counter == last[key] else 1)
                      for key, counter, actor, value in history['ops'])
    if sorted(doc['ops']) != want_ops:
        got = set(doc['ops'])
        return (f"{len(doc['ops'])} ops, {len(want_ops)} recorded, "
                f'{sum(op in got for op in want_ops)} in both')
    want_changes = sorted(history['changes'], key=repr)
    got_changes = sorted(doc['changes'], key=repr)
    if got_changes != want_changes:
        return (f'{len(got_changes)} changes, {len(want_changes)} '
                f'recorded, {sum(c in want_changes for c in got_changes)} '
                'in both')
    return None
