"""Device-resident frontier index: ONE open-addressing hash table over
32-byte change hashes, serving exact membership for the sync plane and
the subscription hub's quiet-tick frontier compare.

The sync protocol's membership questions (``theirHave`` lastSync
reconciliation, received-heads lookup, incoming-change dedup) ride
per-document Python dicts today — O(1) per probe, but each probe forces
the doc's hash-graph dicts to exist (``_ensure_graph``), which is
O(history) to build, and the per-peer probe loops are host work that
grows with the fleet. Following WarpSpeed (PAPERS.md, the technique
source for concurrent GPU open-addressing tables), this module keeps the
whole fleet's (doc, hash) membership in ONE fixed-capacity open-
addressing table with batched, JIT-compiled insert/probe kernels: a full
round's probes are one device dispatch regardless of history length or
peer count — the same O(1)-dispatch property round 6 won for Bloom
build/probe (fleet/bloom.py), extended to exact membership.

Layout and algorithm
--------------------

- Keys are (space, hash) pairs: the 32-byte SHA-256 hash as eight
  little-endian uint32 lanes plus an int32 *space* id. Spaces are
  namespaces (one per doc slot, minted monotonically, never reused) so
  one physical table serves every doc without cross-doc false hits.
- Linear probing over a power-of-two capacity. The batched insert
  resolves intra-batch collisions with a claim scatter: every pending
  row proposes itself (scatter-min of row index) for its empty slot,
  winners write, losers re-probe the same slot next iteration — a loser
  carrying the SAME key then terminates on the match instead of
  double-inserting. Duplicate inserts are therefore idempotent by
  construction, in-batch and across batches.
- Tombstone-free deletion: ``release_space`` only marks the space dead
  (host-side bitmap). Dead keys stay physically resident — probes mask
  dead spaces host-side — and are reclaimed wholesale at the next
  grow-by-migration, which re-inserts only live-space keys into the
  doubled table (one dispatch). No tombstones, no probe-chain breaks.
- Host fallback for the tiny-N case: below ``device_min`` total keys the
  spaces live as plain Python sets (zero dispatches, faster than a
  device round-trip); the first insert crossing the threshold migrates
  everything device-side in one dispatch.

``frontier_compare`` is the second consumer: one dispatch comparing K
cursor head rows against K doc head rows (the ``_DocCols`` columnar
head32/head_n lanes), collapsing the subscription hub's 10k-subscriber
quiet tick into a single device call (query/subscriptions.py).

Every kernel is wrapped in ``instrument_kernel`` so the round-17 cost
ledger and ``obs_report --floor`` see it, and the module registers
dispatch/memory sources like fleet/bloom.py does.
"""

import weakref

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ['HashIndex', 'FleetFrontierIndex', 'PeerSentSet',
           'flush_peer_sets', 'probe_peer_sets', 'release_sent_hashes',
           'release_sync_state', 'frontier_compare', 'hashes_to_rows',
           'engine_hash_rows', 'HistoryIndex', 'dispatch_count',
           'probe_window', 'set_probe_window']

_GOLD = np.uint32(0x9E3779B9)     # Fibonacci-hash mix for the space id

# Device dispatches issued by the batched insert/probe/compare entry
# points since import — the frontier-index twin of bloom.dispatch_count()
# (the table serves host-side protocol drivers, which have no fleet
# dispatch counter in scope). The quiet-tick pin tests
# (tests/test_hashindex.py, tests/test_sync_fabric.py) and chip_smoke.py's
# sync leg diff this around a round.
_dispatches = 0


def dispatch_count():
    """Monotonic count of frontier-index device dispatches (insert +
    probe + migrate + frontier compare)."""
    return _dispatches


# AUTOMERGE_TPU_FRONTIER_INDEX=0 pins the classic host-dict membership
# path EVERYWHERE the index would otherwise serve — the batched driver
# AND the single-doc protocol (backend/sync.py known_hash_flags routes
# through _FlatEngine.probe_hashes, which consults this) — the old-path
# contrast in tests/test_hashindex.py and tests/test_storage_tier.py, and
# a debugging escape hatch. Default on.
import os as _os  # noqa: E402
_frontier_enabled = _os.environ.get('AUTOMERGE_TPU_FRONTIER_INDEX') != '0'


def frontier_enabled():
    return _frontier_enabled


def set_frontier_enabled(on):
    """Toggle frontier-index routing (tests / debugging; returns the
    previous setting). Covers the batched sync driver and the warm
    single-doc probe path alike."""
    global _frontier_enabled
    prev = _frontier_enabled
    _frontier_enabled = bool(on)
    return prev


def _env_int(name, default, lo, hi):
    try:
        val = int(_os.environ.get(name, '') or default)
    except ValueError:
        val = default
    return max(lo, min(hi, val))


# The windowed-probe width and the host/device crossover were both tuned
# against XLA-CPU dispatch overhead (a while_loop iteration costs
# ~0.1 ms there). On-chip both tradeoffs move, so they are env-tunable —
# no code change to re-tune the fabric. Neither has been swept on the chip.
_DEF_PROBE_WINDOW = 16
_DEF_DEVICE_MIN = 4096
_probe_window = _env_int('AUTOMERGE_TPU_PROBE_WINDOW',
                         _DEF_PROBE_WINDOW, 1, 1024)
_default_device_min = _env_int('AUTOMERGE_TPU_DEVICE_MIN',
                               _DEF_DEVICE_MIN, 0, 1 << 30)


def probe_window():
    """Current windowed-probe width (slots gathered per probe before the
    serial tail walk). Set via AUTOMERGE_TPU_PROBE_WINDOW or
    ``set_probe_window``."""
    return _probe_window


def set_probe_window(width):
    """Set the probe window width (tests / on-chip retune);
    returns the previous width. The probe kernel specializes per width
    (static jit arg), so each distinct width compiles once per batch
    shape and is cached thereafter."""
    global _probe_window
    prev = _probe_window
    _probe_window = max(1, min(1024, int(width)))
    return prev


from ..observability import register_dispatch_source  # noqa: E402
from ..observability.metrics import Counters  # noqa: E402
from ..observability.perf import instrument_kernel, register_mem_source  # noqa: E402
from ..observability.spans import spanned as _spanned  # noqa: E402
register_dispatch_source('hashindex', dispatch_count)

_stats = Counters({
    'hashindex_inserts': 0,       # keys newly landed in a table
    'hashindex_probes': 0,        # membership questions answered
    'hashindex_migrations': 0,    # grow-by-migration passes
    'hashindex_promotions': 0,    # host-mode tables promoted to device
    'hashindex_backfills': 0,     # doc registrations (history backfills)
    'hashindex_peer_spaces': 0,   # peer sentHashes spaces minted
    'hashindex_peer_releases': 0,  # peer spaces handed back
})
from ..observability import register_health_source  # noqa: E402
for _key in _stats:
    register_health_source(_key, lambda k=_key: _stats[k])

_live_indexes = weakref.WeakSet()
_live_peer_sets = weakref.WeakSet()


def _index_bytes():
    total = 0
    for ix in list(_live_indexes):
        total += ix.resident_bytes()
    for ps in list(_live_peer_sets):
        total += ps.staged_bytes()
    return total


register_mem_source('hashindex_bytes', _index_bytes)


def _pow2(n, floor=1):
    out = max(int(floor), 1)
    n = int(n)
    while out < n:
        out *= 2
    return out


def hashes_to_rows(hashes):
    """Normalize hash input to an [N, 32] uint8 array: accepts a list of
    hex strings, a list of 32-byte buffers, or an [N, 32] uint8 array
    (returned as-is). One C-level hex decode for the whole batch."""
    if isinstance(hashes, np.ndarray):
        if hashes.dtype != np.uint8 or hashes.ndim != 2 or \
                hashes.shape[1] != 32:
            raise ValueError('hash array must be [N, 32] uint8')
        return hashes
    if not hashes:
        return np.zeros((0, 32), dtype=np.uint8)
    first = hashes[0]
    if isinstance(first, str):
        raw = bytes.fromhex(''.join(hashes))
    else:
        raw = b''.join(bytes(h) for h in hashes)
    if len(raw) != 32 * len(hashes):
        raise ValueError('hashes must be 256 bits')
    return np.frombuffer(raw, dtype=np.uint8).reshape(len(hashes), 32)


def _rows_to_words(rows):
    """[N, 32] uint8 -> [N, 8] uint32 key lanes (little-endian words)."""
    return np.ascontiguousarray(rows).view('<u4').reshape(len(rows), 8)


# ---- kernels ---------------------------------------------------------
# Plain jnp + jax.jit like fleet/bloom.py: the shapes (capacity, padded
# batch) are pow2 so recompiles stay O(log^2). x64 is disabled in this
# deployment, so keys ride as eight uint32 lanes, never uint64.

def _start_pos(keys, spaces, cap):
    mask = jnp.uint32(cap - 1)
    mix = keys[:, 0] ^ (spaces.astype(jnp.uint32) * jnp.uint32(_GOLD))
    return (mix & mask).astype(jnp.int32)


def _insert_kernel(tkey, tspace, keys, spaces, valid):
    """Batched insert of (space, key) pairs into the open-addressing
    table. Returns (tkey, tspace, n_new). Idempotent for keys already
    present (in the table or earlier in the batch)."""
    cap = tkey.shape[0]
    n = keys.shape[0]
    row = jnp.arange(n, dtype=jnp.int32)
    pos = _start_pos(keys, spaces, cap)
    wrap = jnp.int32(cap - 1)

    def cond(state):
        _tk, _ts, _pos, pending, _new = state
        return pending.any()

    def body(state):
        tk, ts, pos, pending, n_new = state
        slot_space = ts[pos]
        occ = slot_space >= 0
        match = pending & occ & (slot_space == spaces) & \
            jnp.all(tk[pos] == keys, axis=-1)
        pending = pending & ~match
        want = pending & ~occ
        # claim each empty slot for exactly one row (lowest index wins);
        # losers retry the SAME slot next iteration so a duplicate key
        # sees its winner's write and terminates on the match
        claim = jnp.full((cap,), n, dtype=jnp.int32)
        claim = claim.at[jnp.where(want, pos, cap)].min(row, mode='drop')
        won = want & (claim[pos] == row)
        wpos = jnp.where(won, pos, cap)
        tk = tk.at[wpos].set(keys, mode='drop')
        ts = ts.at[wpos].set(spaces, mode='drop')
        n_new = n_new + won.sum(dtype=jnp.int32)
        pending = pending & ~won
        advance = pending & occ & ~match
        pos = jnp.where(advance, (pos + 1) & wrap, pos)
        return tk, ts, pos, pending, n_new

    tkey, tspace, _pos, _pending, n_new = jax.lax.while_loop(
        cond, body, (tkey, tspace, pos, valid,
                     jnp.zeros((), dtype=jnp.int32)))
    return tkey, tspace, n_new


def _probe_kernel(tkey, tspace, keys, spaces, valid, window):
    """Batched exact-membership probe; [N] bool (True = present). The
    first `window` slots of every row's chain are gathered and
    compared in ONE vectorized pass (XLA-CPU while_loop iterations cost
    ~0.1ms each in dispatch overhead, so the common short-chain case
    must not loop); only rows still undecided after the window — all
    occupied, no match, possible at high load — take the serial tail
    walk. `window` is a static jit arg (see ``set_probe_window``).
    Sound because slots are never emptied in place (dead spaces
    stay occupied until migration), so a chain scan ending at an empty
    slot is always conclusive."""
    cap = tkey.shape[0]
    wrap = jnp.int32(cap - 1)
    pos0 = _start_pos(keys, spaces, cap)
    w = jnp.arange(window, dtype=jnp.int32)
    win = (pos0[:, None] + w[None, :]) & wrap            # [N, W]
    slot_space = tspace[win]                             # [N, W]
    occ = slot_space >= 0
    match = occ & (slot_space == spaces[:, None]) & \
        jnp.all(tkey[win] == keys[:, None, :], axis=-1)  # [N, W]
    big = jnp.int32(window + 1)
    first_match = jnp.min(jnp.where(match, w[None, :], big), axis=1)
    first_empty = jnp.min(jnp.where(~occ, w[None, :], big), axis=1)
    found = valid & (first_match < first_empty)
    undecided = valid & (first_match == big) & (first_empty == big)

    def cond(state):
        _pos, active, _found = state
        return active.any()

    def body(state):
        pos, active, found = state
        s = tspace[pos]
        occ = s >= 0
        hit = active & occ & (s == spaces) & \
            jnp.all(tkey[pos] == keys, axis=-1)
        found = found | hit
        active = active & occ & ~hit
        pos = jnp.where(active, (pos + 1) & wrap, pos)
        return pos, active, found

    tail_pos = (pos0 + jnp.int32(window)) & wrap
    _pos, _active, found = jax.lax.while_loop(
        cond, body, (tail_pos, undecided, found))
    return found


def _compare_kernel(cur32, cur_n, doc32, doc_n):
    """Quiet iff the cursor frontier equals the doc frontier: head
    counts agree AND (both empty, or the single head32 rows are byte
    equal). Counts past 1 (multi-head) are NEVER quiet here — those
    classes are host residue; answering False routes them there."""
    eq = jnp.all(cur32 == doc32, axis=-1)
    return (cur_n == doc_n) & ((cur_n == 0) | ((cur_n == 1) & eq))


# the table operands are DONATED: an insert's output table reuses the
# input buffers instead of copying capacity-sized arrays per call (the
# old table is dead the moment the wrapper reassigns self._tkey)
_insert_kernel = instrument_kernel(
    'hashindex_insert', jax.jit(_insert_kernel, donate_argnums=(0, 1)))
_probe_kernel = instrument_kernel(
    'hashindex_probe', jax.jit(_probe_kernel, static_argnums=(5,)))
_compare_kernel = instrument_kernel('frontier_compare',
                                    jax.jit(_compare_kernel))


def _pad_batch(words, spaces, valid, floor=8):
    n = len(spaces)
    n_pad = _pow2(n, floor=floor)
    if n_pad == n:
        return words, spaces, valid
    words = np.concatenate(
        [words, np.zeros((n_pad - n, 8), dtype=np.uint32)])
    spaces = np.concatenate(
        [spaces, np.full(n_pad - n, -1, dtype=np.int32)])
    valid = np.concatenate([valid, np.zeros(n_pad - n, dtype=bool)])
    return words, spaces, valid


@_spanned('frontier_compare')
def frontier_compare(cur32, cur_n, doc32, doc_n):
    """ONE device dispatch answering K frontier-equality questions:
    ``out[k]`` is True iff cursor frontier k (head32 row + head count,
    0 = empty, 1 = the row) equals doc frontier k. Inputs are numpy
    ([K, 32] uint8 and [K] int32-ish); rows are pow2-padded. Counts
    other than 0/1 must be resolved host-side by the caller."""
    global _dispatches
    k = len(cur_n)
    if k == 0:
        return np.zeros(0, dtype=bool)
    k_pad = _pow2(k, floor=8)
    c32 = np.zeros((k_pad, 32), dtype=np.uint8)
    c32[:k] = cur32
    d32 = np.zeros((k_pad, 32), dtype=np.uint8)
    d32[:k] = doc32
    cn = np.full(k_pad, -2, dtype=np.int32)
    cn[:k] = cur_n
    dn = np.full(k_pad, -3, dtype=np.int32)
    dn[:k] = doc_n
    out = _compare_kernel(jnp.asarray(c32), jnp.asarray(cn),
                          jnp.asarray(d32), jnp.asarray(dn))
    _dispatches += 1
    return np.asarray(out)[:k]


# ---- the table -------------------------------------------------------

class HashIndex:
    """Open-addressing exact-membership table over (space, 32-byte hash)
    keys. See the module docstring for the layout. Host mode (plain
    sets) below ``device_min`` total keys; device mode past it; both
    modes answer identically (the adversarial suite pins it)."""

    def __init__(self, capacity=1024, device_min=None, load_max=0.6):
        if load_max <= 0 or load_max >= 1:
            raise ValueError('load_max must be in (0, 1)')
        # None -> AUTOMERGE_TPU_DEVICE_MIN (default 4096) so the
        # host/device crossover is re-tunable on-chip without code
        self.device_min = _default_device_min if device_min is None \
            else int(device_min)
        self.load_max = float(load_max)
        self.cap = _pow2(capacity, floor=8)
        self._tkey = None          # [cap, 8] uint32 (device)
        self._tspace = None        # [cap] int32, -1 = empty (device)
        self.occupancy = 0         # physical slots used (incl. dead keys)
        self.n_keys = 0            # live keys (dead spaces excluded)
        self._next_space = 0
        self._live = np.zeros(64, dtype=bool)   # space id -> alive
        self._sets = {}            # host mode: space -> set of 32-byte keys
        self.grows = 0
        _live_indexes.add(self)

    # -- introspection -------------------------------------------------

    @property
    def mode(self):
        return 'host' if self._sets is not None else 'device'

    def resident_bytes(self):
        if self._sets is not None:
            # sets of 32-byte bytes objects: ~80 B object overhead each
            return sum(len(s) for s in self._sets.values()) * 112
        return self.cap * (8 * 4 + 4)

    def __len__(self):
        return self.n_keys

    # -- spaces --------------------------------------------------------

    def new_space(self):
        """Mint a fresh namespace id (never reused)."""
        sid = self._next_space
        self._next_space += 1
        if sid >= len(self._live):
            grown = np.zeros(_pow2(sid + 1, floor=64), dtype=bool)
            grown[:len(self._live)] = self._live
            self._live = grown
        self._live[sid] = True
        if self._sets is not None:
            self._sets[sid] = set()
        return sid

    def release_space(self, sid):
        """Tombstone-free delete of a whole namespace: the space is
        marked dead now (probes mask it host-side); its physical slots
        are reclaimed at the next grow-by-migration."""
        if sid < 0 or sid >= self._next_space or not self._live[sid]:
            return
        self._live[sid] = False
        if self._sets is not None:
            self.n_keys -= len(self._sets.pop(sid, ()))
            self.occupancy = self.n_keys
        # device mode: n_keys for the dead space is unknown per space;
        # the migration recount restores exactness. Until then n_keys is
        # an upper bound, which only ever grows the table early.

    def live_spaces(self):
        return [int(s) for s in np.flatnonzero(self._live)]

    # -- inserts / probes ----------------------------------------------

    def _space_vec(self, spaces, n):
        if np.isscalar(spaces):
            return np.full(n, int(spaces), dtype=np.int32)
        out = np.asarray(spaces, dtype=np.int32)
        if len(out) != n:
            raise ValueError('spaces and hashes must align')
        return out

    def insert(self, spaces, hashes):
        """Insert N (space, hash) pairs — duplicates are no-ops. ONE
        device dispatch in device mode. `spaces` is an int array or a
        scalar broadcast over the batch; `hashes` as in
        ``hashes_to_rows``. Returns the number of NEW keys landed."""
        rows = hashes_to_rows(hashes)
        n = len(rows)
        if n == 0:
            return 0
        spaces = self._space_vec(spaces, n)
        valid = (spaces >= 0) & (spaces < self._next_space) & \
            self._live[np.clip(spaces, 0, len(self._live) - 1)]
        if self._sets is not None and \
                self.n_keys + n <= self.device_min:
            new = 0
            for i in np.flatnonzero(valid).tolist():
                s = self._sets[int(spaces[i])]
                k = rows[i].tobytes()
                if k not in s:
                    s.add(k)
                    new += 1
            self.n_keys += new
            self.occupancy = self.n_keys
            if new:
                _stats.inc('hashindex_inserts', new)
            return new
        if self._sets is not None:
            self._promote()
        self._ensure_capacity(self.occupancy + n)
        new = self._device_insert(_rows_to_words(rows), spaces, valid)
        if new:
            _stats.inc('hashindex_inserts', new)
        return new

    def probe(self, spaces, hashes):
        """[N] bool exact membership — ONE device dispatch in device
        mode. Unknown/dead spaces answer False."""
        rows = hashes_to_rows(hashes)
        n = len(rows)
        if n == 0:
            return np.zeros(0, dtype=bool)
        spaces = self._space_vec(spaces, n)
        valid = (spaces >= 0) & (spaces < self._next_space) & \
            self._live[np.clip(spaces, 0, len(self._live) - 1)]
        _stats.inc('hashindex_probes', n)
        if self._sets is not None:
            out = np.zeros(n, dtype=bool)
            for i in np.flatnonzero(valid).tolist():
                out[i] = rows[i].tobytes() in self._sets[int(spaces[i])]
            return out
        global _dispatches
        words, spaces_p, valid_p = _pad_batch(
            _rows_to_words(rows), spaces, valid)
        hit = _probe_kernel(self._tkey, self._tspace,
                            jnp.asarray(words), jnp.asarray(spaces_p),
                            jnp.asarray(valid_p), _probe_window)
        _dispatches += 1
        return np.asarray(hit)[:n]

    # -- device plumbing -----------------------------------------------

    def _alloc_table(self, cap):
        return (jnp.zeros((cap, 8), dtype=jnp.uint32),
                jnp.full((cap,), -1, dtype=jnp.int32))

    def _device_insert(self, words, spaces, valid):
        global _dispatches
        words, spaces, valid = _pad_batch(words, spaces, valid)
        self._tkey, self._tspace, n_new = _insert_kernel(
            self._tkey, self._tspace, jnp.asarray(words),
            jnp.asarray(spaces), jnp.asarray(valid))
        _dispatches += 1
        new = int(n_new)
        self.occupancy += new
        self.n_keys += new
        return new

    def _promote(self):
        """Host sets -> device table, one insert dispatch."""
        sets, self._sets = self._sets, None
        self._ensure_capacity(self.n_keys, alloc_only=True)
        total = sum(len(s) for s in sets.values())
        self.occupancy = self.n_keys = 0
        _stats.inc('hashindex_promotions')
        if not total:
            return
        rows = np.zeros((total, 32), dtype=np.uint8)
        spaces = np.zeros(total, dtype=np.int32)
        k = 0
        for sid, keys in sets.items():
            for key in keys:
                rows[k] = np.frombuffer(key, dtype=np.uint8)
                spaces[k] = sid
                k += 1
        self._device_insert(_rows_to_words(rows), spaces,
                            np.ones(total, dtype=bool))

    def _ensure_capacity(self, need, alloc_only=False):
        """Grow (pow2) so `need` keys fit under load_max; migration
        re-inserts only LIVE-space keys (dead spaces reclaimed here)."""
        cap = self.cap
        while need > self.load_max * cap:
            cap *= 2
        if self._tkey is None:
            self.cap = cap
            self._tkey, self._tspace = self._alloc_table(cap)
            return
        if cap == self.cap:
            return
        old_key, old_space = self._tkey, self._tspace
        self.cap = cap
        self._tkey, self._tspace = self._alloc_table(cap)
        old_occ = self.occupancy
        self.occupancy = 0
        if alloc_only or old_occ == 0:
            return
        live = self._live[:max(self._next_space, 1)]
        osp = np.asarray(old_space)
        valid = (osp >= 0) & live[np.clip(osp, 0, len(live) - 1)]
        migrated = self._device_insert(np.asarray(old_key), osp, valid)
        self.n_keys = migrated   # exact live recount
        self.grows += 1
        _stats.inc('hashindex_migrations')


# ---- peer sent-spaces ------------------------------------------------

def _release_peer_space(table, sid):
    table.release_space(sid)
    _stats.inc('hashindex_peer_releases')


class PeerSentSet:
    """One peer link's ``sentHashes`` as a *peer-space* of a shared
    ``HashIndex``: a set-like duck type (``in`` / ``add``) whose adds
    STAGE host-side (hex strings, bounded by sent volume) until
    ``flush_peer_sets`` lands every link's backlog in ONE batched
    insert per shard round. Space ids are minted monotonically and
    never reused, so a reconnecting peer can never inherit a
    predecessor's sent set; ``release()`` — and GC, via the finalizer,
    for states dropped without ceremony — hands the space back for the
    next grow-by-migration to reclaim.

    Unlike the plain-set path, the object is shared BY IDENTITY across
    sync-state generations: the classic ``set(sent_hashes)``
    copy-on-write only shielded the OLD state dict, which no caller
    ever re-generates from, and the promotion itself snapshots the old
    plain set — so membership answers are unchanged."""

    __slots__ = ('table', 'sid', '_staged', '_finalizer', '__weakref__')

    def __init__(self, table, seed=()):
        self.table = table
        self.sid = table.new_space()
        self._staged = set(seed)
        self._finalizer = weakref.finalize(
            self, _release_peer_space, table, self.sid)
        _stats.inc('hashindex_peer_spaces')
        _live_peer_sets.add(self)

    @property
    def alive(self):
        return self._finalizer.alive

    def __contains__(self, hash_hex):
        if hash_hex in self._staged:
            return True
        return bool(self.table.probe(self.sid, [hash_hex])[0])

    def add(self, hash_hex):
        self._staged.add(hash_hex)

    def stage_many(self, hashes):
        self._staged.update(hashes)

    def contains_many(self, hashes):
        """[N] bool membership without flushing: staged hashes answer
        host-side, the remainder in one probe."""
        out = np.zeros(len(hashes), dtype=bool)
        rest = []
        for i, h in enumerate(hashes):
            if h in self._staged:
                out[i] = True
            else:
                rest.append(i)
        if rest:
            out[rest] = self.table.probe(
                self.sid, [hashes[i] for i in rest])
        return out

    def flush(self):
        """Land this one link's staged rows (prefer the module-level
        ``flush_peer_sets`` — it batches N links into one insert)."""
        flush_peer_sets([self])

    def release(self):
        """Disconnect / reset: hand the space back (idempotent)."""
        if self._finalizer.alive:
            self._staged.clear()
            self._finalizer()

    def staged_bytes(self):
        # staged hex strings: ~112 B apiece (64-char str + set slot)
        return len(self._staged) * 112


def flush_peer_sets(peer_sets):
    """Land every staged (peer-space, hash) row across N links in ONE
    batched insert per underlying table — THE per-shard-round insert of
    the sync fabric. Returns the number of new keys landed."""
    by_table = {}
    for ps in peer_sets:
        if isinstance(ps, PeerSentSet) and ps._staged and ps.alive:
            by_table.setdefault(id(ps.table), (ps.table, []))[1].append(ps)
    landed = 0
    for table, group in by_table.values():
        spaces, hex_list = [], []
        for ps in group:
            staged = sorted(ps._staged)
            ps._staged.clear()
            spaces.extend([ps.sid] * len(staged))
            hex_list.extend(staged)
        landed += table.insert(np.asarray(spaces, dtype=np.int32),
                               hex_list)
    return landed


def release_sent_hashes(obj):
    """Hand back the peer-space behind a ``sentHashes`` value (no-op for
    plain sets). Call wherever a link's sync state is discarded —
    disconnect, ``reset=True``, stall reset — the GC finalizer would get
    there eventually; deterministic release gets there now."""
    if isinstance(obj, PeerSentSet):
        obj.release()


def release_sync_state(state):
    """``release_sent_hashes`` over a whole sync-state dict."""
    if isinstance(state, dict):
        release_sent_hashes(state.get('sentHashes'))


def probe_peer_sets(peer_sets, hash_lists):
    """Fused sentHashes filter: ``out[i][j]`` is True iff
    ``hash_lists[i][j]`` was already sent on link ``peer_sets[i]``.
    Every link's staged backlog flushes first (at most one insert per
    table), then ALL links' questions ride one probe dispatch per
    table. Released links answer all-False (their space is dead)."""
    flush_peer_sets(peer_sets)
    out = [np.zeros(len(hs), dtype=bool) for hs in hash_lists]
    by_table = {}
    for i, (ps, hs) in enumerate(zip(peer_sets, hash_lists)):
        if hs and isinstance(ps, PeerSentSet):
            by_table.setdefault(id(ps.table), (ps.table, []))[1].append(i)
    for table, idxs in by_table.values():
        spaces, hex_list, owner = [], [], []
        for i in idxs:
            hs = list(hash_lists[i])
            spaces.extend([peer_sets[i].sid] * len(hs))
            hex_list.extend(hs)
            owner.extend([(i, j) for j in range(len(hs))])
        hit = table.probe(np.asarray(spaces, dtype=np.int32), hex_list)
        for (i, j), h in zip(owner, hit):
            out[i][j] = bool(h)
    return out


# ---- fleet wiring ----------------------------------------------------

def engine_hash_rows(engine, since=None):
    """Every APPLIED change hash of a backend engine as [n, 32] uint8
    rows, WITHOUT building the hash-graph query dicts: materialized graph
    keys, then deferred records served from their cheapest byte lane — the
    turbo parser's hash32 rows as they are for pending seam segments and
    staged commits, one ``bytes.fromhex`` over the native extractor's hash
    list for a parked prefix — with a per-change header decode only for
    records that have neither. Queued (causally premature) changes are
    excluded, matching get_change_by_hash. `since`: only the deferred
    records from that one on (what a caller that keeps the population has
    not seen yet)."""
    parts = []   # [k, 32] uint8 arrays and lists of hex strings, in order
    records = engine._deferred
    if since is None:
        if engine.change_index_by_hash:
            parts.append(list(engine.change_index_by_hash))
    else:
        records = records[since:]
    for entry in records:
        if len(entry) != 3:
            parts.append([entry[1]])
            continue
        _index, batch, idxs = entry
        if not isinstance(idxs, (list, tuple, range)):
            idxs = [idxs]
        lanes = getattr(batch, 'm', None)
        if lanes is not None:
            # the turbo parser's hash lanes, as they are (a seam segment's
            # run of them, a staged commit's applied changes)
            parts.append(lanes['hash32'][idxs.start:idxs.stop]
                         if type(idxs) is range and idxs.step == 1
                         else lanes['hash32'][np.asarray(idxs,
                                                         dtype=np.int64)])
            continue
        if getattr(batch, 'engine', None) is engine:
            # a parked prefix: the native extractor's hashes, else the
            # decoded changes' (the materialize fills one of the two;
            # today's sync rounds materialize these docs anyway — the
            # graph walk in get_change_hashes — so this forces nothing new)
            engine._materialize_doc()
            doc_hashes = getattr(engine, '_doc_hashes', None)
            doc_decoded = getattr(engine, '_doc_decoded', None)
            if doc_hashes is not None and type(idxs) is range and \
                    idxs.step == 1 and idxs.stop <= len(doc_hashes):
                parts.append(doc_hashes[idxs.start:idxs.stop])
                continue
            if doc_decoded is not None:
                parts.append([doc_decoded[int(j)]['hash'] for j in idxs])
                continue
        parts.append([batch.resolve(int(j))[0] for j in idxs])
    parts = [hashes_to_rows(part) for part in parts if len(part)]
    if len(parts) == 1:
        return parts[0]
    return np.concatenate(parts) if parts else \
        np.zeros((0, 32), dtype=np.uint8)


class HistoryIndex:
    """Exact membership over ONE document's applied change hashes, on the
    host, for the turbo path's general causal gate (native.general_gate
    reads it in place): the hashes as ``rows[:n]``, [., 32] uint8, and
    over them an open-addressing table of row numbers (int32, -1 free, a
    power of two between 1.5 and 3 entries a hash; the first 8 bytes of a
    hash pick the slot, the 32-byte compare confirms). ``extend`` only
    takes rows in; the gate enters ``rows[entered:n]`` into the table
    before it asks, so a document is fed when it is asked about. Both are
    numpy arrays this object owns, so dropping it frees them; rows grow a
    sixteenth at a time: at most 34 + 12 bytes a hash."""

    __slots__ = ('rows', 'n', 'table', 'entered')

    def __init__(self):
        self.rows = np.empty((0, 32), dtype=np.uint8)
        self.n = self.entered = 0
        self.table = np.full(16, -1, dtype=np.int32)

    @property
    def nbytes(self):
        return self.rows.nbytes + self.table.nbytes

    def extend(self, rows):
        """Take ``rows`` ([k, 32] uint8) into the population."""
        first, n = self.n, self.n + len(rows)
        if n > len(self.rows):
            grown = np.empty((n + max(n >> 4, 32), 32), dtype=np.uint8)
            grown[:first] = self.rows[:first]
            self.rows = grown
        self.rows[first:n] = rows
        self.n = n
        if 3 * n > 2 * len(self.table):
            self.table = np.full(_pow2(n + (n + 1) // 2, 16), -1,
                                 dtype=np.int32)
            self.entered = 0


class FleetFrontierIndex:
    """The per-fleet membership view over one ``HashIndex``: doc slots
    map to table spaces, commits STAGE their (slot, hash32) rows host-
    side (no dispatch on the commit fast path), and the next probe
    flushes the backlog in one insert dispatch. Registration backfills a
    doc's existing history once (cheap lanes, see
    ``engine_hash_rows``); slot frees release the space
    (reclaimed at the next migration — tombstone-free)."""

    def __init__(self, fleet, device_min=None, capacity=1024):
        self._fleet_ref = weakref.ref(fleet)
        self.table = HashIndex(capacity=capacity, device_min=device_min)
        self._spaces = {}          # slot -> space id
        self._staged = []          # (slot int, [n,32] uint8) batches
        self._staged_hex = []      # (slot, hex hash) singles

    # -- registration --------------------------------------------------

    def space_of(self, engine, register=True):
        """The engine's space id, registering (with a one-time history
        backfill) on first use. Returns None for unregistered engines
        when register=False."""
        slot = engine.slot
        sid = self._spaces.get(slot)
        if sid is not None:
            return sid
        if not register:
            return None
        sid = self.table.new_space()
        self._spaces[slot] = sid
        rows = engine_hash_rows(engine)
        _stats.inc('hashindex_backfills')
        if len(rows):
            self.table.insert(sid, rows)
        return sid

    def registered(self, engine):
        return engine.slot in self._spaces

    def drop_slots(self, slots):
        """Slot free/reuse: release the spaces and purge staged rows so
        a recycled slot can never inherit its previous tenant's keys.
        Staged COMMIT batches carry an ndarray of slots per entry, so
        the purge masks per ROW — a batch mixing freed and live docs
        keeps exactly the live docs' rows."""
        gone = np.fromiter((int(s) for s in slots), dtype=np.int64,
                           count=len(slots))
        gone_set = set(gone.tolist())
        if self._staged:
            kept = []
            for slot_arr, rows in self._staged:
                mask = ~np.isin(slot_arr, gone)
                if mask.all():
                    kept.append((slot_arr, rows))
                elif mask.any():
                    kept.append((slot_arr[mask], rows[mask]))
            self._staged = kept
        if self._staged_hex:
            self._staged_hex = [(s, h) for s, h in self._staged_hex
                                if s not in gone_set]
        for slot in slots:
            sid = self._spaces.pop(slot, None)
            if sid is not None:
                self.table.release_space(sid)

    # -- staging (the commit-seam hook) --------------------------------

    def stage_rows(self, slots, hash32):
        """Host-side append of a commit batch's (slot, hash32) rows:
        numpy only, no dispatch — the next probe flushes. `slots` is an
        int array aligned with `hash32` [n, 32] uint8."""
        if len(hash32):
            self._staged.append((np.asarray(slots, dtype=np.int64).copy(),
                                 np.asarray(hash32, dtype=np.uint8).copy()))

    def stage_one(self, slot, hash_hex):
        self._staged_hex.append((int(slot), hash_hex))

    def flush(self):
        """Land every staged row in ONE insert dispatch. Rows for
        unregistered slots are dropped (their history backfills in full
        at registration, so nothing is lost)."""
        if not self._staged and not self._staged_hex:
            return
        staged, self._staged = self._staged, []
        staged_hex, self._staged_hex = self._staged_hex, []
        rows_list, space_list = [], []
        for slots, rows in staged:
            sids = np.array([self._spaces.get(int(s), -1) for s in slots],
                            dtype=np.int32)
            keep = sids >= 0
            if keep.any():
                rows_list.append(rows[keep])
                space_list.append(sids[keep])
        if staged_hex:
            sids = np.array([self._spaces.get(s, -1)
                             for s, _ in staged_hex], dtype=np.int32)
            keep = sids >= 0
            if keep.any():
                rows_list.append(hashes_to_rows(
                    [h for (_s, h), k in zip(staged_hex, keep) if k]))
                space_list.append(sids[keep])
        if rows_list:
            self.table.insert(np.concatenate(space_list),
                              np.concatenate(rows_list))

    # -- probes --------------------------------------------------------

    def probe_pairs(self, engines, hashes):
        """[N] bool membership for N (engine, hex hash) pairs in ONE
        dispatch (plus at most one staged-insert flush). Engines are
        registered (backfilled) on first sight."""
        self.flush()
        spaces = np.fromiter((self.space_of(e) for e in engines),
                             dtype=np.int32, count=len(engines))
        return self.table.probe(spaces, hashes_to_rows(list(hashes)))

    def resident_bytes(self):
        staged = sum(r.nbytes + s.nbytes for s, r in self._staged)
        return self.table.resident_bytes() + staged
