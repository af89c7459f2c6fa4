"""Cut a small fixture out of a recorded ``.xplane.pb``.

    python3 -m benchmark.tools.cut_xplane <in.xplane.pb> <out.xplane.pb> <t0 ns> <t1 ns> [<device plane> ...]

Keeps what the reduction (``benchmark/harness/xplane.py``) reads and nothing
else: of every ``/device:TPU:<n>`` plane (or of those named) the lines ``XLA Ops`` and ``Async XLA
Ops`` with the events that start inside [t0, t1); of ``/host:CPU`` the loop's
annotations that overlap the window, clipped to it; the metadata of the
events kept, with the display name in place of the full instruction text, and
of its properties ``hlo_category``, ``tf_op``, ``source``, ``bytes_accessed``
and ``model_flops``. The fixtures under ``benchmark/fixtures`` were cut with
it; their README says from which trace and where.
"""

import sys

from benchmark.harness.xplane import (ANNOTATIONS, ASYNC_LINE, OPS_LINE,
                                      _raw_fields, _string_field, _text)

KEEP_STATS = {"hlo_category", "tf_op", "source", "bytes_accessed",
              "model_flops"}


def _enc_varint(value):
    out = bytearray()
    while True:
        byte, value = value & 0x7F, value >> 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _field(number, wire, payload):
    if wire == 0:
        return _enc_varint(number << 3) + _enc_varint(payload)
    if wire == 2:
        return (_enc_varint(number << 3 | 2) + _enc_varint(len(payload))
                + bytes(payload))
    return _enc_varint(number << 3 | wire) + bytes(payload)


def _entry(message):
    """A map entry -> (key, value message)."""
    parts = {no: value for no, _, value in _raw_fields(message)}
    return parts[1], parts[2]


def cut_plane(plane, t0, t1, devices=()):
    """The cut plane's bytes, or None for a plane that is not wanted."""
    parts = list(_raw_fields(plane))
    name = _string_field(plane, 2)
    device, host = name.startswith("/device:TPU"), name == "/host:CPU"
    if not (device or host) or (device and devices and name not in devices):
        return None
    stat_names = {}
    for no, _, value in parts:
        if no == 5:
            key, meta = _entry(value)
            stat_names[key] = _string_field(meta, 2)
    names = {}
    for no, _, value in parts:
        if no == 4:
            key, meta = _entry(value)
            found = {f: _text(v) for f, _, v in _raw_fields(meta)
                     if f in (2, 4)}
            names[key] = found.get(4) or found.get(2, "")

    out = bytearray()
    for no, wire, value in parts:
        if no in (1, 2):
            out += _field(no, wire, value)
    used = set()
    for no, _, line in parts:
        if no != 3:
            continue
        fields = list(_raw_fields(line))
        line_name = _string_field(line, 2)
        if device and line_name not in (OPS_LINE, ASYNC_LINE):
            continue
        start_ns = next((v for f, _, v in fields if f == 3), 0)
        kept = bytearray()
        for f, _, event in fields:
            if f != 4:
                continue
            ev = {k: v for k, _, v in _raw_fields(event)}
            meta, offset, duration = ev[1], ev.get(2, 0), ev.get(3, 0)
            begin = start_ns + offset / 1000.0
            end = begin + duration / 1000.0
            if host:
                if names.get(meta) not in ANNOTATIONS or end <= t0 or begin >= t1:
                    continue
                begin, end = max(begin, t0), min(end, t1)
                offset = round((begin - start_ns) * 1000)
                duration = round((end - begin) * 1000)
            elif not t0 <= begin < t1:
                continue
            used.add(meta)
            kept += _field(4, 2, _field(1, 0, meta) + _field(2, 0, offset)
                           + _field(3, 0, duration))
        if kept:
            head = b"".join(_field(f, w, v) for f, w, v in fields
                            if f in (1, 2, 3, 10, 11))
            out += _field(3, 2, head + bytes(kept))

    used_stats = set()
    for no, _, value in parts:
        if no != 4:
            continue
        key, meta = _entry(value)
        if key not in used:
            continue
        fields = list(_raw_fields(meta))
        has_display = any(f == 4 for f, _, _ in fields)
        slim = bytearray()
        for f, w, v in fields:
            if f == 5:
                stat = {k: x for k, _, x in _raw_fields(v)}
                if stat_names.get(stat[1]) not in KEEP_STATS:
                    continue
                used_stats.update({stat[1], stat.get(7, stat[1])})
                slim += _field(5, 2, v)
            elif f in (1, 4) or (f == 2 and not has_display):
                slim += _field(f, w, v)
        out += _field(4, 2, _field(1, 0, key) + _field(2, 2, bytes(slim)))
    for no, _, value in parts:
        if no == 5 and _entry(value)[0] in used_stats:
            out += _field(5, 2, value)
    return bytes(out)


def main(argv=None) -> int:
    src, dst, t0, t1, *devices = (argv or sys.argv[1:])
    with open(src, "rb") as f:
        space = memoryview(f.read())
    out = bytearray()
    for no, _, plane in _raw_fields(space):
        if no == 1:
            cut = cut_plane(plane, float(t0), float(t1), devices)
            if cut is not None:
                out += _field(1, 2, cut)
    with open(dst, "wb") as f:
        f.write(out)
    print(f"wrote {dst}: {len(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
