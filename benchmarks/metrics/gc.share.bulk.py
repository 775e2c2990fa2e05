"""Host runtime: the share of the window spent inside Python's cycle
collector (gc.callbacks around every collection, automatic or explicit).
The fleet's documents are cyclic garbage once dropped, and a full
collection scans everything allocated since set-up."""


def read(ctx):
    facts = ctx['facts']
    if not facts.get('elapsed_s') or 'collector_s' not in facts:
        return None
    return 100.0 * facts['collector_s'] / facts['elapsed_s']
