"""A reader for the ``.xplane.pb`` wire format, for what
``jax.profiler.ProfileData`` does not expose: the metadata of events
(where in the source a device operation comes from).  It follows
tsl/profiler/protobuf/xplane.proto and needs no protobuf library.
"""

import struct


def _varint(buf, at):
    out = shift = 0
    while True:
        b = buf[at]
        at += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, at
        shift += 7


def fields(buf):
    """Yield (field number, wire type, value) over one message; a
    length-delimited value is a memoryview of its bytes."""
    buf = memoryview(buf)
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, at = _varint(buf, at)
        elif wire == 1:
            val, at = struct.unpack_from("<d", buf, at)[0], at + 8
        elif wire == 2:
            n, at = _varint(buf, at)
            val, at = buf[at:at + n], at + n
        elif wire == 5:
            val, at = struct.unpack_from("<f", buf, at)[0], at + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield num, wire, val


def _stat(buf, stat_names):
    name, value = None, None
    for num, _, val in fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num in (5, 6):
            value = bytes(val).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val, val)    # a reference into the table
        else:
            value = val
    return name, value


def _map_entry(buf):
    key = value = None
    for num, _, val in fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def event_metadata(path):
    """{plane name: {event name: {stat name: value}}} for every plane of
    the file; lines and their events are skipped unread."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for num, wire, plane in fields(space):
        if num != 1 or wire != 2:
            continue
        name, metas, stat_names = "", [], {}
        for pnum, _, val in fields(plane):
            if pnum == 2:
                name = bytes(val).decode()
            elif pnum == 4:
                metas.append(_map_entry(val)[1])
            elif pnum == 5:
                sid, smeta = _map_entry(val)
                for snum, _, sval in fields(smeta):
                    if snum == 2:
                        stat_names[sid] = bytes(sval).decode()
        table = {}
        for meta in metas:
            ev_name, stats = "", {}
            for mnum, _, val in fields(meta):
                if mnum == 2:
                    ev_name = bytes(val).decode("utf-8", "replace")
                elif mnum == 5:
                    key, value = _stat(val, stat_names)
                    stats[key] = value
            table[ev_name] = stats
        out[name] = table
    return out
