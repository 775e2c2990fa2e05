"""The benchmark's own writer of the Automerge binary change format, for the
one shape its generators send: a change of ONE ``set`` of an int on a root
key with no predecessor. Written from the format's description
(automerge-classic BINARY_FORMAT.md: chunk container, LEB128, RLE columns);
it imports nothing of ``automerge_tpu``, so what the program parses was not
produced by the program's encoder.
"""

import hashlib

MAGIC = bytes((0x85, 0x6f, 0x4a, 0x83))
CHUNK_CHANGE = 1
# column ids of a change chunk: (column number << 4) | type
COL_KEY_STR = 0x15      # string RLE
COL_INSERT = 0x34       # boolean
COL_ACTION = 0x42       # unsigned RLE
COL_VAL_LEN = 0x56      # value metadata, unsigned RLE
COL_VAL_RAW = 0x57      # value bytes
COL_PRED_NUM = 0x70     # group cardinality
ACTION_SET = 1
VALUE_INT = 4           # low four bits of a value's metadata: sLEB int


def uleb(n):
    out = bytearray()
    while True:
        byte = n & 0x7f
        n >>= 7
        if n:
            out.append(byte | 0x80)
        else:
            out.append(byte)
            return bytes(out)


def sleb(n):
    out = bytearray()
    while True:
        byte = n & 0x7f
        n >>= 7
        if (n == 0 and not byte & 0x40) or (n == -1 and byte & 0x40):
            out.append(byte)
            return bytes(out)
        out.append(byte | 0x80)


# one literal value in an RLE column: run length -1, then the value
_ONE = sleb(-1)
_CHANGE = bytes((CHUNK_CHANGE,))
# time 0 (sLEB), an empty message, no other actors
_NO_TIME_MESSAGE_ACTORS = sleb(0) + uleb(0) + uleb(0)
_SIX_COLUMNS = uleb(6)
_COL_KEY_STR, _COL_VAL_LEN, _COL_VAL_RAW = (
    uleb(COL_KEY_STR), uleb(COL_VAL_LEN), uleb(COL_VAL_RAW))
# insert: one false; action: one literal `set`; predNum: one literal 0
_INSERT_ACTION_DATA = uleb(1) + _ONE + uleb(ACTION_SET)
_INSERT_ACTION_HEAD = uleb(COL_INSERT) + uleb(1) + uleb(COL_ACTION) + uleb(2)
_PRED_DATA = _ONE + uleb(0)
_PRED_HEAD = uleb(COL_PRED_NUM) + uleb(len(_PRED_DATA))


def set_change(actor, seq, start_op, deps, key, value):
    """(bytes, hash) of the change by `actor` (hex) with sequence number
    `seq` whose one op, `start_op`@actor, sets root key `key` to the int
    `value`; `deps` are the hex hashes it follows, and go out sorted."""
    key_bytes = key.encode()
    raw = sleb(value)
    key_col = _ONE + uleb(len(key_bytes)) + key_bytes
    val_len = _ONE + uleb(len(raw) << 4 | VALUE_INT)
    actor_bytes = bytes.fromhex(actor)
    body = b''.join((
        uleb(len(deps)), *(bytes.fromhex(dep) for dep in sorted(deps)),
        uleb(len(actor_bytes)), actor_bytes,
        uleb(seq), uleb(start_op), _NO_TIME_MESSAGE_ACTORS,
        _SIX_COLUMNS,
        _COL_KEY_STR, uleb(len(key_col)),
        _INSERT_ACTION_HEAD,
        _COL_VAL_LEN, uleb(len(val_len)),
        _COL_VAL_RAW, uleb(len(raw)),
        _PRED_HEAD,
        key_col, _INSERT_ACTION_DATA, val_len, raw, _PRED_DATA))
    hashed = _CHANGE + uleb(len(body)) + body
    digest = hashlib.sha256(hashed).digest()
    return MAGIC + digest[:4] + hashed, digest.hex()


# ---------------------------------------------------------------------------
# reading a saved document (chunk type 0), as far as map documents go
# ---------------------------------------------------------------------------

CHUNK_DOCUMENT = 0
COLUMN_DEFLATED = 8
# document columns by id: changes, then ops
CHANGE_ACTOR, CHANGE_SEQ, CHANGE_MAX_OP = 0x01, 0x03, 0x13
CHANGE_DEPS_NUM, CHANGE_DEPS_INDEX = 0x40, 0x43
OP_OBJ_ACTOR, OP_OBJ_CTR, OP_KEY_ACTOR, OP_KEY_CTR = 0x01, 0x02, 0x11, 0x13
OP_KEY_STR, OP_ID_ACTOR, OP_ID_CTR, OP_INSERT = 0x15, 0x21, 0x23, 0x34
OP_ACTION, OP_VAL_LEN, OP_VAL_RAW = 0x42, 0x56, 0x57
OP_SUCC_NUM = 0x80


class Reader:
    def __init__(self, data):
        self.data = data
        self.at = 0

    def done(self):
        return self.at >= len(self.data)

    def take(self, n):
        if self.at + n > len(self.data):
            raise ValueError('document ends inside a field')
        out = self.data[self.at:self.at + n]
        self.at += n
        return out

    def uleb(self):
        shift = value = 0
        while True:
            byte = self.take(1)[0]
            value |= (byte & 0x7f) << shift
            shift += 7
            if not byte & 0x80:
                return value

    def sleb(self):
        shift = value = 0
        while True:
            byte = self.take(1)[0]
            value |= (byte & 0x7f) << shift
            shift += 7
            if not byte & 0x80:
                return value - (1 << shift) if byte & 0x40 else value


def rle(data, read_value):
    """The values of a run-length column; None for a null."""
    reader, out = Reader(data), []
    while not reader.done():
        count = reader.sleb()
        if count > 0:
            out.extend([read_value(reader)] * count)
        elif count < 0:
            out.extend(read_value(reader) for _ in range(-count))
        else:
            out.extend([None] * reader.uleb())
    return out


def rle_uint(data):
    return rle(data, Reader.uleb)


def rle_string(data):
    return rle(data, lambda r: bytes(r.take(r.uleb())).decode())


def delta(data):
    """A delta column: run-length encoded differences, summed."""
    out, last = [], 0
    for step in rle(data, Reader.sleb):
        if step is None:
            out.append(None)
        else:
            last += step
            out.append(last)
    return out


def boolean(data):
    """Alternating run lengths, the first of them of false."""
    reader, out, value = Reader(data), [], False
    while not reader.done():
        out.extend([value] * reader.uleb())
        value = not value
    return out


def padded(values, n):
    """A column that holds nothing but nulls may be left out or cut."""
    return list(values) + [None] * (n - len(values))


def read_document(data):
    """A saved map document, read back by the format's description:
    {'heads': [hex], 'changes': [(actor, seq, max_op, {(actor, seq) of each
    dependency})], 'ops': [(key, counter, actor, value, successors)]} in
    the document's own order. Raises ValueError on anything it does not
    know: another chunk type, a wrong checksum, a value that is no int, an
    op that is no root-map set."""
    import zlib
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
        deps = {names[j] for j in deps_index[at:at + count]}
        at += count
        changes.append((*names[i], max_ops[i], deps))

    counters = col(op_cols, OP_ID_CTR, delta)
    n = len(counters)
    id_actor = col(op_cols, OP_ID_ACTOR, rle_uint, n)
    keys = col(op_cols, OP_KEY_STR, rle_string, n)
    actions = col(op_cols, OP_ACTION, rle_uint, n)
    inserts = col(op_cols, OP_INSERT, boolean, n)
    val_len = col(op_cols, OP_VAL_LEN, rle_uint, n)
    succ_num = col(op_cols, OP_SUCC_NUM, rle_uint, n)
    for column_id, decode in ((OP_OBJ_ACTOR, rle_uint), (OP_OBJ_CTR, rle_uint),
                              (OP_KEY_ACTOR, rle_uint), (OP_KEY_CTR, delta)):
        if any(v is not None for v in col(op_cols, column_id, decode)):
            raise ValueError('an op outside the root map, or on a list')
    raw = Reader(op_cols.get(OP_VAL_RAW, b''))
    ops = []
    for i in range(n):
        if actions[i] != ACTION_SET or inserts[i] or keys[i] is None:
            raise ValueError(f'op {i} is no set of a root map key')
        size, kind = val_len[i] >> 4, val_len[i] & 0xf
        if kind != VALUE_INT:
            raise ValueError(f'op {i} holds a value of type {kind}')
        value = Reader(raw.take(size)).sleb()
        ops.append((keys[i], counters[i], actors[id_actor[i]], value,
                    succ_num[i] or 0))
    return {'heads': heads, 'changes': changes, 'ops': ops}
