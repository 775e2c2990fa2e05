"""The benchmark's own writer and reader of the Automerge binary format for
a Text that TWO actors edit: changes with two dependencies, referents and
predecessors named by (counter, actor), and the saved document of two
actors with two heads. Written from the format's description
(automerge-classic BINARY_FORMAT.md), beside ``wire_text.py`` (one actor),
whose column encoders and document reader it imports; it imports nothing
of ``automerge_tpu``, so what the program parses and loads was not produced
by the program's encoder, and what it saves is read back by another reader.

The document: op 1 is the ``makeText`` at root key ``text``, by the first
writer. A keystroke is (insert?, referent): an insert goes after the
element ``(ref_ctr, ref_actor)`` (counter 0: the head), a delete removes
that element and names its insert op as predecessor. Inside a change an
actor is an index: 0 the change's author, 1 the other writer, who is listed
only if an op of the change names one of its ops (the Text itself is an op
of the first writer, so the second writer's changes always list the first).
Inside a document the two actors are listed in the order of their ids.
"""

import hashlib

import numpy as np

import wire_text
from wire import (CHANGE_ACTOR, CHANGE_DEPS_INDEX, CHANGE_DEPS_NUM,
                  CHANGE_MAX_OP, CHANGE_SEQ, CHUNK_CHANGE, CHUNK_DOCUMENT,
                  MAGIC, OP_ACTION, OP_ID_ACTOR, OP_ID_CTR, OP_INSERT,
                  OP_KEY_ACTOR, OP_KEY_CTR, OP_KEY_STR, OP_OBJ_ACTOR,
                  OP_OBJ_CTR, OP_SUCC_NUM, OP_VAL_LEN, OP_VAL_RAW, Reader,
                  boolean, delta, padded, rle_uint, sleb, uleb)
from wire_text import (ACTION_DEL, ACTION_MAKE_TEXT, ACTION_SET,
                       CHANGE_EXTRA_LEN, CHANGE_MESSAGE, CHANGE_TIME,
                       ONE_CHAR, OP_PRED_ACTOR, OP_PRED_CTR, OP_PRED_NUM,
                       OP_SUCC_ACTOR, OP_SUCC_CTR, TEXT_KEY, TEXT_OBJ_CTR,
                       VALUE_BYTES, boolean_column, container, delta_column,
                       rle_column)

read_text_document = wire_text.read_text_document
make_text_change = wire_text.make_text_change


# ---------------------------------------------------------------------------
# changes of many keystrokes (the history's rounds)
# ---------------------------------------------------------------------------
# A round's chain is 1 to 64 keystrokes, and a history holds thousands of
# them: at that length wire_text's numpy encoders spend their time in
# numpy's overheads, so the same encodings are written out here over plain
# lists (the tests hold the two families equal, byte for byte).

def rle_list(values, signed=False):
    """wire_text.rle_column over a list; None is a null."""
    if all(v is None for v in values):
        return b''
    leb = sleb if signed else uleb
    out, lone = [], []
    i, n = 0, len(values)

    def flush():
        if lone:
            out.append(sleb(-len(lone)))
            out.extend(leb(v) for v in lone)
            del lone[:]

    while i < n:
        j = i
        while j < n and values[j] == values[i]:
            j += 1
        if values[i] is None:
            flush()
            out.append(b'\x00' + uleb(j - i))
        elif j - i == 1:
            lone.append(values[i])
        else:
            flush()
            out.append(sleb(j - i) + leb(values[i]))
        i = j
    flush()
    return b''.join(out)


def delta_list(values):
    """wire_text.delta_column over a list; None is a null."""
    steps, last = [], 0
    for v in values:
        if v is None:
            steps.append(None)
        else:
            steps.append(v - last)
            last = v
    return rle_list(steps, signed=True)


def boolean_list(values):
    """wire_text.boolean_column over a list."""
    out, at, run = [], False, 0
    for v in values:
        if bool(v) == at:
            run += 1
        else:
            out.append(uleb(run))
            at, run = not at, 1
    if values:
        out.append(uleb(run))
    return b''.join(out)


def round_columns(ops, writer):
    """What a change of keystrokes holds besides its characters: (column
    info and the data before the characters, the data after them, whether
    it lists the other actor). `ops` are (insert?, referent) pairs, a
    referent the code `counter * 2 + writer` of the element an insert goes
    after (0: the head) or a delete removes; `writer` (0 made the Text) is
    the author."""
    n = len(ops)
    is_insert = [ins for ins, _ref in ops]
    ref_ctr = [ref >> 1 for _ins, ref in ops]
    # an actor inside a change: 0 the author, 1 the other writer
    ref_actor = [None if not ref else int((ref & 1) != writer)
                 for _ins, ref in ops]
    obj_actor = writer
    gone = [(ctr, who) for ins, ctr, who in zip(is_insert, ref_ctr,
                                                ref_actor) if not ins]
    columns = sorted((cid, data) for cid, data in (
        (OP_OBJ_ACTOR, rle_list([obj_actor] * n)),
        (OP_OBJ_CTR, rle_list([TEXT_OBJ_CTR] * n)),
        (OP_KEY_ACTOR, rle_list(ref_actor)),
        (OP_KEY_CTR, delta_list(ref_ctr)),
        (OP_INSERT, boolean_list(is_insert)),
        (OP_ACTION, rle_list([ACTION_SET if ins else ACTION_DEL
                              for ins in is_insert])),
        (OP_VAL_LEN, rle_list([ONE_CHAR if ins else 0
                               for ins in is_insert])),
        (OP_VAL_RAW, b'?' * sum(is_insert)),
        (OP_PRED_NUM, rle_list([0 if ins else 1 for ins in is_insert])),
        (OP_PRED_ACTOR, rle_list([who for _ctr, who in gone])),
        (OP_PRED_CTR, delta_list([ctr for ctr, _who in gone])),
    ) if data)
    info = uleb(len(columns)) + b''.join(
        uleb(cid) + uleb(len(data)) for cid, data in columns)
    before = b''.join(data for cid, data in columns if cid < OP_VAL_RAW)
    after = b''.join(data for cid, data in columns if cid > OP_VAL_RAW)
    return info + before, after, bool(obj_actor or any(ref_actor))


def round_change(actor_bytes, other_bytes, seq, start_op, deps, columns,
                 chars):
    """(bytes, hash as bytes) of a change of keystrokes: `columns` from
    round_columns, `chars` the inserted characters' bytes in op order,
    `deps` the hashes it follows (bytes, sorted)."""
    before, after, lists_other = columns
    body = b''.join((
        uleb(len(deps)), *deps, uleb(len(actor_bytes)), actor_bytes,
        uleb(seq), uleb(start_op), b'\x00\x00',
        b'\x01' + uleb(len(other_bytes)) + other_bytes if lists_other
        else b'\x00', before, chars, after))
    hashed = b'\x01' + uleb(len(body)) + body
    digest = hashlib.sha256(hashed).digest()
    return MAGIC + digest[:4] + hashed, digest


# ---------------------------------------------------------------------------
# a change of ONE keystroke, by hand: every column holds one literal value
# ---------------------------------------------------------------------------

_ONE = sleb(-1)
_SHAPES = {}


def _literal(value):
    return _ONE + uleb(value)


def _keystroke_shape(is_insert, obj_actor, key_actor, ctr_size):
    """(column info, data before the key counter, data between it and the
    last field, data after that) of a one-keystroke change whose key
    counter takes `ctr_size` bytes; `key_actor` None names the head. An
    insert's character and a delete's predecessor counter go last."""
    key_ctr = b'?' * (1 + ctr_size)
    key_actor_col = b'' if key_actor is None else _literal(key_actor)
    if is_insert:
        columns = [
            (OP_OBJ_ACTOR, _literal(obj_actor)),
            (OP_OBJ_CTR, _literal(TEXT_OBJ_CTR)),
            (OP_KEY_ACTOR, key_actor_col), (OP_KEY_CTR, key_ctr),
            (OP_INSERT, b'\x00\x01'), (OP_ACTION, _literal(ACTION_SET)),
            (OP_VAL_LEN, _literal(ONE_CHAR)), (OP_VAL_RAW, b'?'),
            (OP_PRED_NUM, _literal(0))]
    else:
        columns = [
            (OP_OBJ_ACTOR, _literal(obj_actor)),
            (OP_OBJ_CTR, _literal(TEXT_OBJ_CTR)),
            (OP_KEY_ACTOR, key_actor_col), (OP_KEY_CTR, key_ctr),
            (OP_INSERT, b'\x01'), (OP_ACTION, _literal(ACTION_DEL)),
            (OP_VAL_LEN, _literal(0)), (OP_PRED_NUM, _literal(1)),
            (OP_PRED_ACTOR, _literal(key_actor)), (OP_PRED_CTR, key_ctr)]
    columns = [c for c in columns if c[1]]
    info = uleb(len(columns)) + b''.join(
        uleb(cid) + uleb(len(data)) for cid, data in columns)
    ids = [cid for cid, _data in columns]
    at = ids.index(OP_KEY_CTR)
    before = b''.join(data for _cid, data in columns[:at]) + _ONE
    last = ids.index(OP_VAL_RAW if is_insert else OP_PRED_CTR)
    middle = b''.join(data for _cid, data in columns[at + 1:last])
    after = b''.join(data for _cid, data in columns[last + 1:])
    return info, before, middle + (b'' if is_insert else _ONE), after


def keystroke_change(actor_bytes, other_bytes, author_made_text, seq,
                     start_op, deps, is_insert, ref_ctr, ref_other, char):
    """(bytes, hash as bytes) of the change whose one op, `start_op`, is a
    keystroke. `actor_bytes` and `other_bytes` are the two writers' ids,
    `deps` the hashes it follows (bytes, one or two, in any order);
    `ref_other` says the referent is the other writer's element."""
    ctr = sleb(ref_ctr)
    obj_actor = 0 if author_made_text else 1
    key_actor = None if not ref_ctr else int(bool(ref_other))
    shape = (is_insert, obj_actor, key_actor, len(ctr))
    if shape not in _SHAPES:
        _SHAPES[shape] = _keystroke_shape(*shape)
    info, before, middle, after = _SHAPES[shape]
    if len(deps) == 1:
        deps_part = b'\x01' + deps[0]
    else:
        first, second = deps
        deps_part = b'\x02' + (first + second if first < second
                               else second + first)
    others = b'\x01' + uleb(len(other_bytes)) + other_bytes \
        if obj_actor or key_actor else b'\x00'
    body = b''.join((
        deps_part, uleb(len(actor_bytes)), actor_bytes, uleb(seq),
        uleb(start_op), b'\x00\x00', others, info, before, ctr, middle,
        char if is_insert else ctr, after))
    hashed = b'\x01' + uleb(len(body)) + body
    digest = hashlib.sha256(hashed).digest()
    return MAGIC + digest[:4] + hashed, digest


def read_keystrokes_change(data):
    """A change of keystrokes by one of two writers, read back (for the
    round-trip tests): {'hash', 'deps', 'actor', 'others': [hex], 'seq',
    'start_op', 'ops': [(insert?, referent counter, referent's actor (hex)
    or None for the head, character or None)]}."""
    data = bytes(data)
    if data[:4] != MAGIC:
        raise ValueError('no magic bytes')
    chunk = Reader(data[8:])
    kind, length = chunk.take(1)[0], chunk.uleb()
    raw_body = data[8 + chunk.at:]
    if kind != CHUNK_CHANGE or length != len(raw_body):
        raise ValueError(f'chunk type {kind}: not one plain change')
    digest = hashlib.sha256(data[8:]).digest()
    if digest[:4] != data[4:8]:
        raise ValueError('checksum does not match')
    body = Reader(raw_body)
    deps = [body.take(32).hex() for _ in range(body.uleb())]
    actor = body.take(body.uleb()).hex()
    seq, start_op = body.uleb(), body.uleb()
    body.sleb()                       # time
    body.take(body.uleb())            # message
    others = [body.take(body.uleb()).hex() for _ in range(body.uleb())]
    table = [actor] + others
    info = [(body.uleb(), body.uleb()) for _ in range(body.uleb())]
    cols = {cid: body.take(size) for cid, size in info}
    inserts = boolean(cols.get(OP_INSERT, b''))
    n = len(inserts)
    obj_actor = padded(rle_uint(cols.get(OP_OBJ_ACTOR, b'')), n)
    obj_ctr = padded(rle_uint(cols.get(OP_OBJ_CTR, b'')), n)
    key_actor = padded(rle_uint(cols.get(OP_KEY_ACTOR, b'')), n)
    key_ctr = padded(delta(cols.get(OP_KEY_CTR, b'')), n)
    actions = padded(rle_uint(cols.get(OP_ACTION, b'')), n)
    pred_num = padded(rle_uint(cols.get(OP_PRED_NUM, b'')), n)
    pred_actor = rle_uint(cols.get(OP_PRED_ACTOR, b''))
    pred_ctr = delta(cols.get(OP_PRED_CTR, b''))
    chars = iter(cols.get(OP_VAL_RAW, b''))
    ops, at = [], 0
    for i in range(n):
        if obj_ctr[i] != TEXT_OBJ_CTR or \
                actions[i] != (ACTION_SET if inserts[i] else ACTION_DEL):
            raise ValueError(f'op {i} is no keystroke on the Text')
        made_by = table[obj_actor[i]]
        who = None if not key_ctr[i] else table[key_actor[i]]
        if not inserts[i]:
            if pred_num[i] != 1 or (pred_ctr[at], table[pred_actor[at]]) != \
                    (key_ctr[i], who):
                raise ValueError(f'delete {i} does not name its target as '
                                 'its one predecessor')
            at += 1
        ops.append((inserts[i], key_ctr[i] or 0, who,
                    chr(next(chars)) if inserts[i] else None))
    return {'hash': digest.hex(), 'deps': deps, 'actor': actor,
            'others': others, 'seq': seq, 'start_op': start_op, 'ops': ops,
            'text_made_by': made_by if n else None}


# ---------------------------------------------------------------------------
# the saved document of two writers
# ---------------------------------------------------------------------------

def text_document(actors, heads, changes, head_index, elem_ctr, elem_writer,
                  ref_ctr, ref_writer, chars, succ_num, succ_ctr,
                  succ_writer):
    """The saved document of two writers' history. `actors` are the two
    writers' hex ids (writer 0 made the Text, op 1); `heads` the hex hashes
    of the heads, in their order, and `head_index` their changes' indexes;
    `changes` the columns `writer`, `seq`, `max_op` and the list `deps` of
    index lists, in an order in which every dependency comes before its
    dependent.

    Its ops are the makeText and then every element of the Text in
    sequence order: `elem_ctr` / `elem_writer` the inserting op, `ref_ctr`
    / `ref_writer` the element it was inserted after (counter 0: the head),
    `chars` its character's byte, `succ_num` how many ops deleted it and
    `succ_ctr` / `succ_writer` those ops, element after element, each
    element's in the order of their ids."""
    order = sorted(range(2), key=lambda w: actors[w])
    index_of = np.empty(2, dtype=np.int64)       # writer -> document index
    index_of[order] = np.arange(2)
    writer, seq, max_op, deps = (changes['writer'], changes['seq'],
                                 changes['max_op'], changes['deps'])
    n_changes, n = len(seq), len(elem_ctr)
    deps_num = np.fromiter((len(d) for d in deps), dtype=np.int64,
                           count=n_changes)
    deps_flat = np.fromiter((i for d in deps for i in d), dtype=np.int64,
                            count=int(deps_num.sum()))
    elem_ctr = np.asarray(elem_ctr, dtype=np.int64)
    ref_ctr = np.asarray(ref_ctr, dtype=np.int64)
    first = np.r_[True, np.zeros(n, dtype=bool)]      # the makeText's row
    change_columns = [
        (CHANGE_ACTOR, rle_column(index_of[np.asarray(writer)])),
        (CHANGE_SEQ, delta_column(seq)),
        (CHANGE_MAX_OP, delta_column(max_op)),
        (CHANGE_TIME, delta_column(np.zeros(n_changes))),
        # every message the empty string
        (CHANGE_MESSAGE, sleb(n_changes) + uleb(0) if n_changes > 1
         else sleb(-1) + uleb(0)),
        (CHANGE_DEPS_NUM, rle_column(deps_num)),
        (CHANGE_DEPS_INDEX, delta_column(deps_flat)),
        (CHANGE_EXTRA_LEN, rle_column(np.full(n_changes, VALUE_BYTES))),
    ]
    key = TEXT_KEY.encode()
    text_actor = index_of[0]
    op_columns = [
        (OP_OBJ_ACTOR, rle_column(np.full(n + 1, text_actor), null=first)),
        (OP_OBJ_CTR, rle_column(np.full(n + 1, TEXT_OBJ_CTR), null=first)),
        (OP_KEY_ACTOR, rle_column(np.r_[0, index_of[np.asarray(ref_writer)]],
                                  null=np.r_[True, ref_ctr == 0])),
        (OP_KEY_CTR, delta_column(np.r_[0, ref_ctr], null=first)),
        (OP_KEY_STR, sleb(-1) + uleb(len(key)) + key +
         (sleb(0) + uleb(n) if n else b'')),
        (OP_ID_ACTOR, rle_column(
            np.r_[text_actor, index_of[np.asarray(elem_writer)]])),
        (OP_ID_CTR, delta_column(np.r_[TEXT_OBJ_CTR, elem_ctr])),
        (OP_INSERT, boolean_column(~first)),
        (OP_ACTION, rle_column(np.where(first, ACTION_MAKE_TEXT,
                                        ACTION_SET))),
        (OP_VAL_LEN, rle_column(np.where(first, 0, ONE_CHAR))),
        (OP_VAL_RAW, bytes(chars)),
        (OP_SUCC_NUM, rle_column(np.r_[0, succ_num])),
        (OP_SUCC_ACTOR, rle_column(index_of[np.asarray(succ_writer,
                                                       dtype=np.int64)])),
        (OP_SUCC_CTR, delta_column(succ_ctr)),
    ]
    change_columns = wire_text._deflated([c for c in change_columns if c[1]])
    op_columns = wire_text._deflated([c for c in op_columns if c[1]])
    body = b''.join((
        uleb(2), *(uleb(len(actors[w]) // 2) + bytes.fromhex(actors[w])
                   for w in order),
        uleb(len(heads)), *(bytes.fromhex(head) for head in heads),
        wire_text._column_info(change_columns),
        wire_text._column_info(op_columns),
        *(data for _cid, data in change_columns),
        *(data for _cid, data in op_columns),
        *(uleb(i) for i in head_index)))
    return container(CHUNK_DOCUMENT, body)[0]
