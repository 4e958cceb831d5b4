"""Read a ``jax.profiler`` trace file (``.xplane.pb``) with nothing but
Python: the few messages of the XSpace protocol buffer, decoded from the
wire format.

``jax.profiler.ProfileData`` (which ``trace_reduce.py`` reads with) gives
an event's own stats but not those of its metadata, and on a TPU the name
scope of an operation (``tf_op``: ``jit(_decode_fn)/.../kv_read/
dynamic_slice:``) is a stat of the metadata.  So the readers that need
scopes, or the arguments of the program's dispatch annotations, read the
file here.  No JAX, no process of its own.

    planes = read(path)
    planes[i].name, planes[i].lines[j].name
    planes[i].lines[j].events() -> (name, start_s, end_s, stats) ...

Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``.
"""

from __future__ import annotations

import struct
from typing import Dict, Iterator, List, Tuple


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a
    varint or a fixed-width field, a memoryview for a length-delimited
    one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wire == 5:
            value = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    """One XStat: its name and its value (a reference to a stat's
    metadata stands for that metadata's name, as the profiler interns
    repeated strings)."""
    name, value = "", None
    for number, _wire, v in fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = _text(v)
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


class Line:
    def __init__(self, plane: "Plane", buf):
        self.plane = plane
        self.name = ""
        self.timestamp_ns = 0
        self._events: List[object] = []
        for number, _wire, v in fields(buf):
            if number == 2:
                self.name = _text(v)
            elif number == 3:
                self.timestamp_ns = v
            elif number == 4:
                self._events.append(v)

    def __len__(self) -> int:
        return len(self._events)

    def events(self, stats: bool = True):
        """(name, start_s, end_s, stats) of every event; ``stats`` holds the
        event's own stats over those of its metadata (``stats=False``: the
        metadata's alone, which costs nothing for each event)."""
        plane = self.plane
        base = self.timestamp_ns
        for buf in self._events:
            meta = offset_ps = duration_ps = 0
            own = []
            for number, _wire, v in fields(buf):
                if number == 1:
                    meta = v
                elif number == 2:
                    offset_ps = v
                elif number == 3:
                    duration_ps = v
                elif number == 4 and stats:
                    own.append(v)
            name, meta_stats = plane.event_metadata.get(meta, ("", {}))
            if own:
                meta_stats = dict(meta_stats)
                meta_stats.update(_stat(s, plane.stat_names) for s in own)
            start = base / 1e9 + offset_ps / 1e12
            yield name, start, start + duration_ps / 1e12, meta_stats


class Plane:
    def __init__(self, buf):
        self.name = ""
        self.stat_names: Dict[int, str] = {}
        self.event_metadata: Dict[int, Tuple[str, Dict[str, object]]] = {}
        lines, metas = [], []
        for number, _wire, v in fields(buf):
            if number == 2:
                self.name = _text(v)
            elif number == 3:
                lines.append(v)
            elif number == 4:
                metas.append(v)
            elif number == 5:
                for n2, _w, entry in fields(v):
                    if n2 == 2:
                        ident, name = 0, ""
                        for n3, _w3, x in fields(entry):
                            if n3 == 1:
                                ident = x
                            elif n3 == 2:
                                name = _text(x)
                        self.stat_names[ident] = name
        for entry in metas:
            for n2, _w, value in fields(entry):
                if n2 != 2:
                    continue
                ident, name, stats = 0, "", []
                for n3, _w3, x in fields(value):
                    if n3 == 1:
                        ident = x
                    elif n3 == 2:
                        name = _text(x)
                    elif n3 == 5:
                        stats.append(x)
                self.event_metadata[ident] = (
                    name, dict(_stat(s, self.stat_names) for s in stats))
        self.lines = [Line(self, b) for b in lines]

    def line(self, name: str):
        return [ln for ln in self.lines if ln.name == name]


def read(path: str) -> List[Plane]:
    with open(path, "rb") as f:
        data = memoryview(f.read())
    return [Plane(v) for number, _wire, v in fields(data) if number == 1]
