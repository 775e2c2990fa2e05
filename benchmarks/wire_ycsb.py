"""The benchmark's own writer and reader of the Automerge binary format for
the map store (map-store-ycsb): a record is a map document whose fields hold
strings. It writes the two change shapes the store's generator sends, the
load (ONE change of a set on every field, no predecessor) and an update (ONE
set of one field, whose predecessor is the field's last op), and reads a
saved record back. Written from the format's description (automerge-classic
BINARY_FORMAT.md), with wire.py's LEB128 and column readers; it imports
nothing of ``automerge_tpu``.
"""

import hashlib
import zlib

from wire import (ACTION_SET, CHANGE_ACTOR, CHANGE_DEPS_INDEX,
                  CHANGE_DEPS_NUM, CHANGE_MAX_OP, CHANGE_SEQ, CHUNK_CHANGE,
                  CHUNK_DOCUMENT, COL_ACTION, COL_INSERT, COL_KEY_STR,
                  COL_PRED_NUM, COL_VAL_LEN, COL_VAL_RAW, COLUMN_DEFLATED,
                  MAGIC, OP_ACTION, OP_ID_ACTOR, OP_ID_CTR, OP_INSERT,
                  OP_KEY_STR, OP_OBJ_ACTOR, OP_OBJ_CTR, OP_SUCC_NUM,
                  OP_VAL_LEN, OP_VAL_RAW, Reader, boolean, delta, padded,
                  rle_string, rle_uint, sleb, uleb)

COL_PRED_ACTOR = 0x71   # unsigned RLE: the pred's index in the actor list
COL_PRED_CTR = 0x73     # delta: the pred's counter
VALUE_UTF8 = 6          # low four bits of a value's metadata: a string

_CHANGE = bytes((CHUNK_CHANGE,))
_TIME_MESSAGE = sleb(0) + uleb(0)


def column(column_id, data):
    return uleb(column_id) + uleb(len(data)), data


def _field_names(fields):
    return [f'field{i}'.encode() for i in range(fields)]


def _chunk(body):
    """(buffer, hash): a change chunk of `body` with its checksum."""
    hashed = _CHANGE + _uleb(len(body)) + body
    digest = hashlib.sha256(hashed).digest()
    return MAGIC + digest[:4] + hashed, digest


class LoadWriter:
    """The load of a record: ONE change by the loader (sequence number 1,
    ops 1..fields) that sets every field, `field<i>`, to its string. Every
    record's load change is the same bytes but for the values, so the
    bytes around them are made once."""

    def __init__(self, loader, fields, field_bytes):
        names = _field_names(fields)
        run = sleb(fields)
        before = (column(COL_KEY_STR, sleb(-fields) + b''.join(
                      uleb(len(n)) + n for n in names)),
                  column(COL_INSERT, uleb(fields)),
                  column(COL_ACTION, run + uleb(ACTION_SET)),
                  column(COL_VAL_LEN,
                         run + uleb(field_bytes << 4 | VALUE_UTF8)))
        after = column(COL_PRED_NUM, run + uleb(0))
        raw_meta = uleb(COL_VAL_RAW) + uleb(fields * field_bytes)
        self.head = b''.join((
            uleb(0), uleb(len(loader)), loader, uleb(1), uleb(1),
            _TIME_MESSAGE, uleb(0), uleb(len(before) + 2),
            *(meta for meta, _ in before), raw_meta, after[0],
            *(data for _, data in before)))
        self.tail = after[1]

    def change(self, values):
        """(buffer, hash) of the load change whose values are the bytes
        `values`, fields x field_bytes of them in field order."""
        return _chunk(self.head + values + self.tail)


class UpdateWriter:
    """An update: ONE change by a client's actor that sets one field to a
    string. Its one dependency is the record's head, and its op's one
    predecessor is the field's last op (counter and actor), which is the
    change's own actor or the one other actor it lists. Actors are given by
    their index in `actors` (16 bytes each); the bytes that do not depend
    on the change are made once, and LEB128 numbers below 2^16 are looked
    up."""

    def __init__(self, fields, field_bytes, actors):
        one = sleb(-1)
        self.actor = [uleb(len(a)) + a for a in actors]
        self.other = [_TIME_MESSAGE + uleb(1) + uleb(len(a)) + a
                      for a in actors]
        self.own = _TIME_MESSAGE + uleb(0)
        fixed = (column(COL_INSERT, uleb(1)),
                 column(COL_ACTION, one + uleb(ACTION_SET)),
                 column(COL_VAL_LEN, one + uleb(field_bytes << 4 |
                                                VALUE_UTF8)))
        raw_meta = uleb(COL_VAL_RAW) + uleb(field_bytes)
        pred_num = column(COL_PRED_NUM, one + uleb(1))
        # [field][0: pred by another actor, 1: by the change's own]
        self.meta, self.data = [], []
        for name in _field_names(fields):
            key = column(COL_KEY_STR, one + uleb(len(name)) + name)
            self.data.append(key[1] + b''.join(d for _, d in fixed))
            self.meta.append([b''.join((
                uleb(8), key[0], *(m for m, _ in fixed), raw_meta,
                pred_num[0], column(COL_PRED_ACTOR, one + uleb(index))[0]))
                for index in (1, 0)])
        self.tail = [pred_num[1] + one + uleb(index) for index in (1, 0)]

    def change(self, actor, seq, start_op, dep, field, value, pred_ctr,
               pred_actor):
        """(buffer, hash). `dep` is the 32-byte hash the change follows,
        `value` the field's new bytes, (pred_ctr, pred_actor) the op it
        overwrites."""
        own = pred_actor == actor
        ctr_meta, ctr_data = _pred_ctr_column(pred_ctr)
        return _chunk(b''.join((
            b'\x01', dep, self.actor[actor], _uleb(seq), _uleb(start_op),
            self.own if own else self.other[pred_actor],
            self.meta[field][own], ctr_meta, self.data[field], value,
            self.tail[own], ctr_data)))


_TABLE = 1 << 16
_ULEB = [uleb(n) for n in range(_TABLE)]
_PRED_CTR = [column(COL_PRED_CTR, sleb(-1) + sleb(n)) for n in range(_TABLE)]


def _uleb(n):
    return _ULEB[n] if n < _TABLE else uleb(n)


def _pred_ctr_column(counter):
    """The predecessor counters' delta column of one op: its metadata and
    data."""
    return _PRED_CTR[counter] if counter < _TABLE else \
        column(COL_PRED_CTR, sleb(-1) + sleb(counter))


def read_record(data):
    """A saved record, read back by the format's description: {'heads':
    [hex], 'changes': [(actor, seq, max_op, {(actor, seq) of each
    dependency})], 'ops': [(key, counter, actor, value, successors)]} in
    the document's own order. Raises ValueError on anything it does not
    know: another chunk type, a wrong checksum, a value that is no string,
    an op that is no root-map set."""
    data = bytes(data)
    if data[:4] != MAGIC:
        raise ValueError('no magic bytes')
    chunk = Reader(data[8:])
    kind, length = chunk.take(1)[0], chunk.uleb()
    start = 8 + chunk.at
    if kind != CHUNK_DOCUMENT or start + length != len(data):
        raise ValueError(f'chunk type {kind}, {length} bytes of '
                         f'{len(data) - start}: not one document chunk')
    if hashlib.sha256(data[8:]).digest()[:4] != data[4:8]:
        raise ValueError('checksum does not match')
    body = Reader(data[start:])
    actors = [body.take(body.uleb()).hex() for _ in range(body.uleb())]
    heads = [body.take(32).hex() for _ in range(body.uleb())]
    infos = [[(body.uleb(), body.uleb()) for _ in range(body.uleb())]
             for _group in range(2)]
    groups = []
    for info in infos:
        columns = {}
        for column_id, size in info:
            raw = body.take(size)
            if column_id & COLUMN_DEFLATED:
                raw = zlib.decompress(raw, wbits=-15)
            columns[column_id & ~COLUMN_DEFLATED] = raw
        groups.append(columns)
    change_cols, op_cols = groups

    def col(columns, column_id, decode, n=None):
        values = decode(columns.get(column_id, b''))
        return values if n is None else padded(values, n)

    seqs = col(change_cols, CHANGE_SEQ, delta)
    n = len(seqs)
    change_actor = col(change_cols, CHANGE_ACTOR, rle_uint, n)
    max_ops = col(change_cols, CHANGE_MAX_OP, delta, n)
    deps_num = col(change_cols, CHANGE_DEPS_NUM, rle_uint, n)
    deps_index = col(change_cols, CHANGE_DEPS_INDEX, delta)
    names = [(actors[change_actor[i]], seqs[i]) for i in range(n)]
    changes, at = [], 0
    for i in range(n):
        count = deps_num[i] or 0
        changes.append((*names[i], max_ops[i],
                        {names[j] for j in deps_index[at:at + count]}))
        at += count

    counters = col(op_cols, OP_ID_CTR, delta)
    n = len(counters)
    id_actor = col(op_cols, OP_ID_ACTOR, rle_uint, n)
    keys = col(op_cols, OP_KEY_STR, rle_string, n)
    actions = col(op_cols, OP_ACTION, rle_uint, n)
    inserts = col(op_cols, OP_INSERT, boolean, n)
    val_len = col(op_cols, OP_VAL_LEN, rle_uint, n)
    succ_num = col(op_cols, OP_SUCC_NUM, rle_uint, n)
    for column_id in (OP_OBJ_ACTOR, OP_OBJ_CTR):
        if any(v is not None for v in col(op_cols, column_id, rle_uint)):
            raise ValueError('an op outside the root map')
    raw = Reader(op_cols.get(OP_VAL_RAW, b''))
    ops = []
    for i in range(n):
        if actions[i] != ACTION_SET or inserts[i] or keys[i] is None:
            raise ValueError(f'op {i} is no set of a root map key')
        size, kind = val_len[i] >> 4, val_len[i] & 0xf
        if kind != VALUE_UTF8:
            raise ValueError(f'op {i} holds a value of type {kind}')
        ops.append((keys[i], counters[i], actors[id_actor[i]],
                    bytes(raw.take(size)).decode(), succ_num[i] or 0))
    return {'heads': heads, 'changes': changes, 'ops': ops}
