"""The plain reference of causal delivery, independent of the code under
test: it imports nothing of ``automerge_tpu``.

A change names the changes it follows (its dependencies, by hash). A
document applies a change once every dependency is applied, and holds it
back until then (automerge-classic ``backend/new.js`` applyChanges: the
queue, ``:1569-1571``, and the loop that gates it again after every pass
that applied something, ``:1825-1841``). Changes are delivered in calls; a
call gates what it brings and then what waited, in that order, again and
again until a pass applies nothing: the fixed point. What is still waiting
then is the queue; the dependencies that neither are applied nor wait are
the missing ones (``getMissingDeps``), which the sync protocol asks for
again. The heads are the applied changes that no applied change follows.
"""


class Causal:
    """One document: ``deliver`` calls in order, then ``applied`` (hashes,
    a set), ``order`` (the hashes applied since the start, in the order
    they were), ``queue`` ([(hash, deps)] still waiting, in waiting order),
    ``heads`` (a set) and ``missing()``."""

    def __init__(self, applied=(), heads=()):
        self.applied = set(applied)
        self.heads = set(heads)
        self.order = []
        self.queue = []

    def deliver(self, changes):
        """`changes`: [(hash, [the hashes it follows])] as delivered.
        Returns how many changes this call applied."""
        waiting = list(changes) + self.queue
        before = len(self.order)
        while waiting:
            still = []
            moved = False
            for change, deps in waiting:
                if change in self.applied:
                    continue                  # delivered twice: once
                if all(dep in self.applied for dep in deps):
                    self.applied.add(change)
                    self.order.append(change)
                    self.heads.difference_update(deps)
                    self.heads.add(change)
                    moved = True
                else:
                    still.append((change, deps))
            waiting = still
            if not moved:
                break
        self.queue = waiting
        return len(self.order) - before

    def missing(self):
        waits = {change for change, _deps in self.queue}
        return sorted({dep for _change, deps in self.queue for dep in deps
                       if dep not in self.applied and dep not in waits})
