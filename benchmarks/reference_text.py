"""The plain reference of the text configurations, independent of the code
under test: it imports nothing of ``automerge_tpu``.

A Text is a replicated growable array (RGA; automerge-classic
``backend/new.js`` seekWithinBlock, and the paper it cites: Roh et al.,
"Replicated abstract data types", 2011): a linked list of elements, each
named by the id of the op that inserted it. An insert names a referent (the
element it goes after, or the head) and lands after it, past every element
already there whose id is greater, ids compared Lamport-wise by (counter,
actor); a delete marks its target and leaves it in the list. The text is
the walk of the list, marked elements left out. Ops come in a causal order:
a referent or a target is in the list before the op that names it.
"""

HEAD = None


class Rga:
    """One Text: ``insert`` and ``delete`` in a causal order, then ``text``
    and ``elements``."""

    def __init__(self):
        self._next = {HEAD: None}      # element id -> the id after it
        self._char = {}
        self._deleted_by = {}          # element id -> [ids of its deletes]

    def insert(self, op_id, referent, char):
        """Element `op_id` after `referent` (HEAD or an element's id),
        skipping elements with a greater id. An id is (counter, actor), or
        the counter alone where one actor wrote them all."""
        if referent not in self._next:
            raise KeyError(f'insert {op_id} after {referent}, which is not '
                           'in the list')
        after, nxt = referent, self._next[referent]
        while nxt is not None and nxt > op_id:
            after, nxt = nxt, self._next[nxt]
        self._next[op_id] = nxt
        self._next[after] = op_id
        self._char[op_id] = char
        self._deleted_by[op_id] = []

    def delete(self, op_id, target):
        if target not in self._char:
            raise KeyError(f'delete {op_id} of {target}, which is not in '
                           'the list')
        self._deleted_by[target].append(op_id)

    def elements(self):
        """[(element id, character, [ids of the ops that deleted it])] in
        list order, deleted elements too."""
        out, at = [], self._next[HEAD]
        while at is not None:
            out.append((at, self._char[at], self._deleted_by[at]))
            at = self._next[at]
        return out

    def text(self):
        out, at = [], self._next[HEAD]
        while at is not None:
            if not self._deleted_by[at]:
                out.append(self._char[at])
            at = self._next[at]
        return ''.join(out)
