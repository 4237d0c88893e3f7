"""What jax.profiler.ProfileData does not show of an .xplane.pb: the stats
of a device plane's EVENT METADATA, where the TPU profiler keeps each HLO
operation's provenance — the stat 'tf_op' holds its op_name, e.g.
'jit(decode_step)/.../kv_block_attention/dot_general' once the program
lowers each Fluid op under jax.named_scope (an operation's own event stats
are device_offset_ps, device_duration_ps and a time scale, nothing more).

A protobuf wire-format walk over just those fields (tsl/profiler/protobuf/
xplane.proto: XSpace.planes=1; XPlane.name=2, .event_metadata=4,
.stat_metadata=5; XEventMetadata.name=2, .stats=5; XStatMetadata.name=2;
XStat.metadata_id=1, .str_value=5, .ref_value=7). Lines and events are
skipped by their length, so a large trace costs what its metadata costs."""
from __future__ import annotations


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: varints as ints,
    length-delimited fields as memoryviews, fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 1:
            v, i = None, i + 8
        elif wt == 5:
            v, i = None, i + 4
        else:
            raise ValueError('wire type %d' % wt)
        yield num, wt, v


def _map_entry(buf):
    key = val = None
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def op_provenance(path, plane_prefix='/device:'):
    """{plane name: {event (HLO operation) name: op_name}} for the planes
    whose name starts with `plane_prefix`; only events that carry a
    'tf_op' stat are in it."""
    with open(path, 'rb') as f:
        space = memoryview(f.read())
    out = {}
    for num, wt, plane in _fields(space):
        if num != 1 or wt != 2:
            continue
        name, events, stat_names = '', [], {}
        for pnum, pwt, v in _fields(plane):
            if pnum == 2 and pwt == 2:
                name = bytes(v).decode('utf-8', 'replace')
            elif pnum == 4 and pwt == 2:
                events.append(_map_entry(v)[1])
            elif pnum == 5 and pwt == 2:
                key, val = _map_entry(v)
                for snum, swt, sv in _fields(val):
                    if snum == 2 and swt == 2:
                        stat_names[key] = bytes(sv).decode('utf-8',
                                                           'replace')
        if not name.startswith(plane_prefix):
            continue
        tf_op = {k for k, v in stat_names.items() if v == 'tf_op'}
        ops = out.setdefault(name, {})
        for ev in events:
            ev_name, prov = '', None
            for num2, wt2, v in _fields(ev):
                if num2 == 2 and wt2 == 2:
                    ev_name = bytes(v).decode('utf-8', 'replace')
                elif num2 == 5 and wt2 == 2:
                    stat = {n: x for n, _, x in _fields(v)}
                    if stat.get(1) in tf_op:
                        if stat.get(5) is not None:
                            prov = bytes(stat[5]).decode('utf-8', 'replace')
                        elif stat.get(7) is not None:
                            prov = stat_names.get(stat[7])
            if prov:
                ops[ev_name] = prov
    return out
