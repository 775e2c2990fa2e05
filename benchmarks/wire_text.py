"""The benchmark's own writer and reader of the Automerge binary format for
ONE shape of document: a root map whose key ``text`` holds one Text object
that a single actor edits one character at a time. Written from the format's
description (automerge-classic BINARY_FORMAT.md: chunk container, LEB128,
RLE / delta / boolean columns, the document chunk's change and op columns);
it imports ``wire.py``'s primitives and nothing of ``automerge_tpu``, so what
the program loads and parses was not produced by the program's encoder, and
what it saves is read back by another reader.

Op ids: op 1 is the ``makeText``; the keystroke with trace index t (from 1)
is op t + 1, and an inserted character's elemId is its op's id. A keystroke
is (insert?, ref): an insert goes after the element ``ref`` (0: the head), a
delete removes the element ``ref`` and names its insert op as predecessor.

The column encoders are numpy over whole columns (a document here has
140,000 rows, a history change 1,024 ops); ``keystroke_change`` is the
by-hand form of the same encoding for a change of one op, which the tests
hold against the general writer byte for byte.
"""

import hashlib
import zlib

import numpy as np

from wire import (CHANGE_ACTOR, CHANGE_DEPS_INDEX, CHANGE_DEPS_NUM,
                  CHANGE_MAX_OP, CHANGE_SEQ, CHUNK_CHANGE, CHUNK_DOCUMENT,
                  COLUMN_DEFLATED, MAGIC, OP_ACTION, OP_ID_ACTOR, OP_ID_CTR,
                  OP_INSERT, OP_KEY_ACTOR, OP_KEY_CTR, OP_KEY_STR,
                  OP_OBJ_ACTOR, OP_OBJ_CTR, OP_SUCC_NUM, OP_VAL_LEN,
                  OP_VAL_RAW, Reader, boolean, delta, padded, rle_string,
                  rle_uint, sleb, uleb)

# further column ids: (column number << 4) | type
CHANGE_TIME, CHANGE_MESSAGE = 0x23, 0x35
CHANGE_EXTRA_LEN = 0x56
OP_PRED_NUM, OP_PRED_ACTOR, OP_PRED_CTR = 0x70, 0x71, 0x73
OP_SUCC_ACTOR, OP_SUCC_CTR = 0x81, 0x83
ACTION_SET, ACTION_DEL, ACTION_MAKE_TEXT = 1, 3, 4
VALUE_UTF8, VALUE_BYTES = 6, 7
ONE_CHAR = 1 << 4 | VALUE_UTF8      # valLen of a one-byte string
DEFLATE_MIN_SIZE = 256              # columns and changes at least this long
TEXT_KEY = 'text'
TEXT_OBJ_CTR = 1                    # the makeText is op 1


# ---------------------------------------------------------------------------
# column encoders over numpy arrays
# ---------------------------------------------------------------------------

def leb128(values, signed):
    """LEB128 of every value, concatenated: `values` int64, `signed` a bool
    or a bool array (sLEB where true, else uLEB of a value >= 0)."""
    values = np.asarray(values, dtype=np.int64)
    if not len(values):
        return b''
    signed = np.broadcast_to(np.asarray(signed, dtype=bool), values.shape)
    # bytes needed: unsigned, 7 bits a byte; signed, the last byte's bit 6
    # is the sign, so a value v >= 0 and the value -v - 1 need the same
    n_bytes = 1 + np.where(
        signed,
        np.searchsorted(_SIGNED_STEPS, values ^ (values >> 63), side='right'),
        np.searchsorted(_UNSIGNED_STEPS, values, side='right'))
    k = np.arange(int(n_bytes.max()), dtype=np.int64)
    groups = (values[:, None] >> (7 * k)) & 0x7f
    groups |= np.where(k < n_bytes[:, None] - 1, 0x80, 0)
    return groups.astype(np.uint8)[k < n_bytes[:, None]].tobytes()


_UNSIGNED_STEPS = 1 << (np.arange(1, 9, dtype=np.int64) * 7)
_SIGNED_STEPS = 1 << (np.arange(1, 9, dtype=np.int64) * 7 - 1)


def rle_column(values, signed=False, null=None):
    """The bytes of a run-length column: a run of two or more equal values
    as (count, value), consecutive lone values as (-count, values...), a run
    of nulls as (0, count); a column of nothing but nulls is empty."""
    values = np.asarray(values, dtype=np.int64)
    n = len(values)
    null = np.zeros(n, dtype=bool) if null is None else np.asarray(null)
    if n == 0 or null.all():
        return b''
    values = np.where(null, 0, values)
    new_run = np.r_[True, (values[1:] != values[:-1]) |
                    (null[1:] != null[:-1])]
    starts = np.flatnonzero(new_run)
    lengths = np.diff(np.r_[starts, n])
    run_value, run_null = values[starts], null[starts]
    lone = ~run_null & (lengths == 1)
    group_start = lone & ~np.r_[False, lone[:-1]]
    group_of = np.cumsum(group_start) - 1
    group_size = np.bincount(group_of[lone], minlength=1)
    # tokens: a head (count) and a value for every run but a lone value
    # inside a group, which has its value alone
    has_head = ~lone | group_start
    first = np.cumsum(np.r_[0, has_head[:-1] + 1])
    n_tokens = int(first[-1]) + int(has_head[-1]) + 1
    tokens = np.zeros(n_tokens, dtype=np.int64)
    token_signed = np.zeros(n_tokens, dtype=bool)
    heads = first[has_head]
    tokens[heads] = np.where(
        run_null, 0, np.where(lone, -group_size[np.maximum(group_of, 0)],
                              lengths))[has_head]
    token_signed[heads] = True
    value_at = first + has_head
    tokens[value_at] = np.where(run_null, lengths, run_value)
    token_signed[value_at] = signed & ~run_null
    return leb128(tokens, token_signed)


def delta_column(values, null=None):
    """A delta column: the run-length column of the differences between
    successive values that are not null."""
    values = np.asarray(values, dtype=np.int64)
    if null is None:
        return rle_column(np.diff(values, prepend=0), signed=True)
    null = np.asarray(null)
    steps = np.zeros(len(values), dtype=np.int64)
    steps[~null] = np.diff(values[~null], prepend=0)
    return rle_column(steps, signed=True, null=null)


def boolean_column(values):
    """Alternating run lengths, the first of them of false."""
    values = np.asarray(values, dtype=bool)
    if not len(values):
        return b''
    starts = np.flatnonzero(np.r_[True, values[1:] != values[:-1]])
    lengths = np.diff(np.r_[starts, len(values)])
    if values[0]:
        lengths = np.r_[0, lengths]
    return leb128(lengths, False)


# ---------------------------------------------------------------------------
# changes
# ---------------------------------------------------------------------------

def container(kind, body):
    """(bytes, hash) of one chunk: magic, checksum, type, length, body."""
    hashed = bytes((kind,)) + uleb(len(body)) + body
    digest = hashlib.sha256(hashed).digest()
    return MAGIC + digest[:4] + hashed, digest.hex()


def change_head(actor, seq, start_op, deps):
    """A change's body before its columns: dependencies (sorted), actor,
    sequence number, first opId, time 0, no message, no other actors."""
    actor_bytes = bytes.fromhex(actor)
    return b''.join((
        uleb(len(deps)), *(bytes.fromhex(dep) for dep in sorted(deps)),
        uleb(len(actor_bytes)), actor_bytes, uleb(seq), uleb(start_op),
        sleb(0), uleb(0), uleb(0)))


def with_columns(columns):
    """Column info and data of the columns that are not empty, in id
    order."""
    columns = sorted((cid, data) for cid, data in columns if data)
    return b''.join((
        uleb(len(columns)),
        *(uleb(cid) + uleb(len(data)) for cid, data in columns),
        *(data for _cid, data in columns)))


def make_text_change(actor):
    """(bytes, hash) of the first change: op 1 makes the Text at root key
    ``text``."""
    key = TEXT_KEY.encode()
    one = sleb(-1)
    return container(CHUNK_CHANGE, change_head(actor, 1, 1, []) +
                     with_columns([
                         (OP_KEY_STR, one + uleb(len(key)) + key),
                         (OP_INSERT, uleb(1)),
                         (OP_ACTION, one + uleb(ACTION_MAKE_TEXT)),
                         (OP_VAL_LEN, one + uleb(0)),
                         (OP_PRED_NUM, one + uleb(0))]))


def keystroke_columns(is_insert, ref_ctr):
    """The columns of a change's keystrokes that do not depend on the
    characters typed: [(column id, bytes)] for ops that insert after element
    `ref_ctr` (0: the head) or delete element `ref_ctr`."""
    is_insert = np.asarray(is_insert, dtype=bool)
    ref_ctr = np.asarray(ref_ctr, dtype=np.int64)
    n = len(is_insert)
    deleted = ref_ctr[~is_insert]
    return [
        (OP_OBJ_ACTOR, rle_column(np.zeros(n))),
        (OP_OBJ_CTR, rle_column(np.full(n, TEXT_OBJ_CTR))),
        (OP_KEY_ACTOR, rle_column(np.zeros(n), null=ref_ctr == 0)),
        (OP_KEY_CTR, delta_column(ref_ctr)),
        (OP_INSERT, boolean_column(is_insert)),
        (OP_ACTION, rle_column(np.where(is_insert, ACTION_SET, ACTION_DEL))),
        (OP_VAL_LEN, rle_column(np.where(is_insert, ONE_CHAR, 0))),
        (OP_PRED_NUM, rle_column(~is_insert)),
        (OP_PRED_ACTOR, rle_column(np.zeros(len(deleted)))),
        (OP_PRED_CTR, delta_column(deleted)),
    ]


def keystrokes_change(actor, seq, start_op, deps, columns, chars):
    """(bytes, hash) of a change of keystrokes: `columns` from
    keystroke_columns, `chars` the inserted characters' bytes in op
    order."""
    return container(CHUNK_CHANGE, change_head(actor, seq, start_op, deps) +
                     with_columns(columns + [(OP_VAL_RAW, bytes(chars))]))


# a change of ONE keystroke, by hand: every column holds one literal value
_ONE = sleb(-1)
_ZERO = _ONE + uleb(0)
_SHAPES = {}


def _keystroke_shape(is_insert, has_actor, ctr_size):
    """(column info, data before the key counter, data after it) of a
    one-keystroke change whose key counter takes `ctr_size` bytes; an
    insert's character and a delete's predecessor counter go last."""
    key_ctr = b'?' * (1 + ctr_size)
    if is_insert:
        columns = [
            (OP_OBJ_ACTOR, _ZERO), (OP_OBJ_CTR, _ONE + uleb(TEXT_OBJ_CTR)),
            (OP_KEY_ACTOR, _ZERO if has_actor else b''),
            (OP_KEY_CTR, key_ctr), (OP_INSERT, b'\x00\x01'),
            (OP_ACTION, _ONE + uleb(ACTION_SET)),
            (OP_VAL_LEN, _ONE + uleb(ONE_CHAR)), (OP_VAL_RAW, b'?'),
            (OP_PRED_NUM, _ZERO)]
    else:
        columns = [
            (OP_OBJ_ACTOR, _ZERO), (OP_OBJ_CTR, _ONE + uleb(TEXT_OBJ_CTR)),
            (OP_KEY_ACTOR, _ZERO if has_actor else b''),
            (OP_KEY_CTR, key_ctr), (OP_INSERT, b'\x01'),
            (OP_ACTION, _ONE + uleb(ACTION_DEL)), (OP_VAL_LEN, _ZERO),
            (OP_PRED_NUM, _ONE + uleb(1)), (OP_PRED_ACTOR, _ZERO),
            (OP_PRED_CTR, key_ctr)]
    columns = [c for c in columns if c[1]]
    info = uleb(len(columns)) + b''.join(
        uleb(cid) + uleb(len(data)) for cid, data in columns)
    ids = [cid for cid, _data in columns]
    at = ids.index(OP_KEY_CTR)
    before = b''.join(data for _cid, data in columns[:at]) + _ONE
    # an insert: ... keyCtr | insert action valLen | char | predNum
    # a delete:  ... keyCtr | insert action valLen predNum predActor | ctr
    last = ids.index(OP_VAL_RAW if is_insert else OP_PRED_CTR)
    middle = b''.join(data for _cid, data in columns[at + 1:last])
    after = b''.join(data for _cid, data in columns[last + 1:])
    return info, before, middle + (b'' if is_insert else _ONE), after


def keystroke_change(actor_bytes, seq, start_op, dep, is_insert, ref_ctr,
                     char):
    """(bytes, hash as bytes) of the change whose one op, `start_op`, is a
    keystroke; `actor_bytes` and `dep` (the hash it follows) are bytes."""
    ctr = sleb(ref_ctr)
    shape = (is_insert, ref_ctr != 0, len(ctr))
    if shape not in _SHAPES:
        _SHAPES[shape] = _keystroke_shape(*shape)
    info, before, middle, after = _SHAPES[shape]
    body = b''.join((
        b'\x01', dep, uleb(len(actor_bytes)), actor_bytes, uleb(seq),
        uleb(start_op), b'\x00\x00\x00', info, before, ctr, middle,
        char if is_insert else ctr, after))
    hashed = b'\x01' + uleb(len(body)) + body
    digest = hashlib.sha256(hashed).digest()
    return MAGIC + digest[:4] + hashed, digest


# ---------------------------------------------------------------------------
# the saved document
# ---------------------------------------------------------------------------

def _deflated(columns):
    out = []
    for cid, data in columns:
        if len(data) >= DEFLATE_MIN_SIZE:
            squeeze = zlib.compressobj(6, zlib.DEFLATED, -15)
            cid, data = cid | COLUMN_DEFLATED, \
                squeeze.compress(data) + squeeze.flush()
        out.append((cid, data))
    return out


def _column_info(columns):
    return uleb(len(columns)) + b''.join(
        uleb(cid) + uleb(len(data)) for cid, data in columns)


def text_document(actor, head, max_ops, elem_ctr, ref_ctr, chars, deleted_by):
    """The saved document of one actor's linear history: change i + 1 (of
    len(max_ops)) ends at op max_ops[i] and follows change i; `head` is the
    last one's hash. Its ops are the makeText and then every element of the
    Text in sequence order: `elem_ctr` the inserting op, `ref_ctr` the
    element it was inserted after (0: the head), `chars` its character's
    byte, `deleted_by` the op that deleted it (0: none)."""
    n_changes, n = len(max_ops), len(elem_ctr)
    elem_ctr = np.asarray(elem_ctr, dtype=np.int64)
    ref_ctr = np.asarray(ref_ctr, dtype=np.int64)
    deleted_by = np.asarray(deleted_by, dtype=np.int64)
    first = np.r_[True, np.zeros(n, dtype=bool)]      # the makeText's row
    change_columns = [
        (CHANGE_ACTOR, rle_column(np.zeros(n_changes))),
        (CHANGE_SEQ, delta_column(np.arange(1, n_changes + 1))),
        (CHANGE_MAX_OP, delta_column(max_ops)),
        (CHANGE_TIME, delta_column(np.zeros(n_changes))),
        # every message the empty string
        (CHANGE_MESSAGE, sleb(n_changes) + uleb(0) if n_changes > 1
         else sleb(-1) + uleb(0)),
        (CHANGE_DEPS_NUM, rle_column(np.r_[0, np.ones(n_changes - 1)])),
        (CHANGE_DEPS_INDEX, delta_column(np.arange(n_changes - 1))),
        (CHANGE_EXTRA_LEN, rle_column(np.full(n_changes, VALUE_BYTES))),
    ]
    key = TEXT_KEY.encode()
    op_columns = [
        (OP_OBJ_ACTOR, rle_column(np.zeros(n + 1), null=first)),
        (OP_OBJ_CTR, rle_column(np.full(n + 1, TEXT_OBJ_CTR), null=first)),
        (OP_KEY_ACTOR, rle_column(np.zeros(n + 1),
                                  null=np.r_[True, ref_ctr == 0])),
        (OP_KEY_CTR, delta_column(np.r_[0, ref_ctr], null=first)),
        (OP_KEY_STR, sleb(-1) + uleb(len(key)) + key +
         (sleb(0) + uleb(n) if n else b'')),
        (OP_ID_ACTOR, rle_column(np.zeros(n + 1))),
        (OP_ID_CTR, delta_column(np.r_[TEXT_OBJ_CTR, elem_ctr])),
        (OP_INSERT, boolean_column(~first)),
        (OP_ACTION, rle_column(np.where(first, ACTION_MAKE_TEXT,
                                        ACTION_SET))),
        (OP_VAL_LEN, rle_column(np.where(first, 0, ONE_CHAR))),
        (OP_VAL_RAW, bytes(chars)),
        (OP_SUCC_NUM, rle_column(np.r_[0, deleted_by > 0])),
        (OP_SUCC_ACTOR, rle_column(np.zeros(int((deleted_by > 0).sum())))),
        (OP_SUCC_CTR, delta_column(deleted_by[deleted_by > 0])),
    ]
    change_columns = _deflated([c for c in change_columns if c[1]])
    op_columns = _deflated([c for c in op_columns if c[1]])
    actor_bytes = bytes.fromhex(actor)
    body = b''.join((
        uleb(1), uleb(len(actor_bytes)), actor_bytes,
        uleb(1), bytes.fromhex(head),
        _column_info(change_columns), _column_info(op_columns),
        *(data for _cid, data in change_columns),
        *(data for _cid, data in op_columns),
        uleb(n_changes - 1)))         # the head's index among the changes
    return container(CHUNK_DOCUMENT, body)[0]


def _columns(body, infos):
    groups = []
    for info in infos:
        columns = {}
        for column_id, size in info:
            raw = body.take(size)
            if column_id & COLUMN_DEFLATED:
                raw = zlib.decompress(raw, wbits=-15)
            columns[column_id & ~COLUMN_DEFLATED] = raw
        groups.append(columns)
    return groups


def read_text_document(data):
    """A saved one-Text document, read back by the format's description:
    {'actors': [hex], 'heads': [hex], 'changes': [(actor, seq, max_op,
    {(actor, seq) of each dependency})], 'elements': [(counter, actor,
    referent (counter, actor) or None for the head, character, [(counter,
    actor) of each successor])]} with the elements in the document's own
    order. Raises ValueError on anything else: another chunk type, a wrong
    checksum, a first op that is not the makeText at root key ``text``, a
    later op that is no one-character insert into that Text."""
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
    change_cols, op_cols = _columns(body, infos)

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
    obj_actor = col(op_cols, OP_OBJ_ACTOR, rle_uint, n)
    obj_ctr = col(op_cols, OP_OBJ_CTR, rle_uint, n)
    key_actor = col(op_cols, OP_KEY_ACTOR, rle_uint, n)
    key_ctr = col(op_cols, OP_KEY_CTR, delta, n)
    key_str = col(op_cols, OP_KEY_STR, rle_string, n)
    actions = col(op_cols, OP_ACTION, rle_uint, n)
    inserts = col(op_cols, OP_INSERT, boolean, n)
    val_len = col(op_cols, OP_VAL_LEN, rle_uint, n)
    succ_num = col(op_cols, OP_SUCC_NUM, rle_uint, n)
    succ_actor = col(op_cols, OP_SUCC_ACTOR, rle_uint)
    succ_ctr = col(op_cols, OP_SUCC_CTR, delta)
    raw = op_cols.get(OP_VAL_RAW, b'')
    if not n or actions[0] != ACTION_MAKE_TEXT or key_str[0] != TEXT_KEY or \
            obj_ctr[0] is not None or inserts[0]:
        raise ValueError('the first op does not make the Text at root '
                         f'key {TEXT_KEY!r}')
    text = (counters[0], id_actor[0])
    if len(raw) != n - 1:
        raise ValueError(f'{len(raw)} bytes of values for {n - 1} elements')
    elements, at = [], 0
    for i in range(1, n):
        if (obj_ctr[i], obj_actor[i]) != text or actions[i] != ACTION_SET \
                or not inserts[i] or val_len[i] != ONE_CHAR \
                or key_str[i] is not None:
            raise ValueError(f'op {i} is no one-character insert into '
                             'the Text')
        count = succ_num[i] or 0
        elements.append((
            counters[i], actors[id_actor[i]],
            (key_ctr[i], actors[key_actor[i]]) if key_ctr[i] else None,
            chr(raw[i - 1]),
            [(succ_ctr[j], actors[succ_actor[j]])
             for j in range(at, at + count)]))
        at += count
    return {'actors': actors, 'heads': heads, 'changes': changes,
            'elements': elements}


def read_keystrokes_change(data):
    """A change of keystrokes read back (for the round-trip tests):
    {'hash', 'deps', 'actor', 'seq', 'start_op', 'ops': [(insert?, referent
    counter, character or None)]}. A change of at least 256 bytes may come
    deflated (chunk type 2)."""
    data = bytes(data)
    chunk = Reader(data[8:])
    kind, length = chunk.take(1)[0], chunk.uleb()
    raw_body = data[8 + chunk.at:]
    if kind == 2:
        raw_body = zlib.decompress(raw_body, wbits=-15)
        data = data[:8] + bytes((CHUNK_CHANGE,)) + uleb(len(raw_body)) + \
            raw_body
    elif kind != CHUNK_CHANGE or length != len(raw_body):
        raise ValueError(f'chunk type {kind}: not one change')
    digest = hashlib.sha256(data[8:]).digest()
    if digest[:4] != data[4:8]:
        raise ValueError('checksum does not match')
    body = Reader(raw_body)
    deps = [body.take(32).hex() for _ in range(body.uleb())]
    actor = body.take(body.uleb()).hex()
    seq, start_op = body.uleb(), body.uleb()
    body.sleb()                       # time
    body.take(body.uleb())            # message
    if body.uleb():
        raise ValueError('a change by more than one actor')
    info = [(body.uleb(), body.uleb()) for _ in range(body.uleb())]
    (cols,) = _columns(body, [info])
    inserts = boolean(cols.get(OP_INSERT, b''))
    key_ctr = padded(delta(cols.get(OP_KEY_CTR, b'')), len(inserts))
    chars = iter(cols.get(OP_VAL_RAW, b''))
    return {'hash': digest.hex(), 'deps': deps, 'actor': actor, 'seq': seq,
            'start_op': start_op,
            'ops': [(ins, ctr or 0, chr(next(chars)) if ins else None)
                    for ins, ctr in zip(inserts, key_ctr)]}
