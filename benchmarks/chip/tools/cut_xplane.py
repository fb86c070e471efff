#!/usr/bin/env python3
"""Cuts a recorded ``.xplane.pb`` down to a test fixture.

    python3 cut_xplane.py <in.xplane.pb> <out.xplane.pb> [events_per_line]

Keeps the first N events of every line of every plane and only the event
metadata those events name (the HLO op names are most of a trace's bytes).
Works on the protobuf wire format directly, so it needs no schema module:
XSpace.planes=1; XPlane.lines=3, .event_metadata=4 (map<int64, XEventMetadata>);
XLine.events=4; XEvent.metadata_id=1.
"""

from __future__ import annotations

import sys


def varint(buf: bytes, i: int) -> tuple[int, int]:
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if not b & 0x80:
            return n, i
        shift += 7


def enc_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def fields(buf: bytes):
    """(field number, wire type, value, raw bytes of the whole field)."""
    i = 0
    while i < len(buf):
        start = i
        key, i = varint(buf, i)
        fno, wt = key >> 3, key & 7
        if wt == 0:
            val, i = varint(buf, i)
        elif wt == 1:
            val, i = buf[i : i + 8], i + 8
        elif wt == 2:
            ln, i = varint(buf, i)
            val, i = buf[i : i + ln], i + ln
        elif wt == 5:
            val, i = buf[i : i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield fno, wt, val, buf[start:i]


def ld(fno: int, payload: bytes) -> bytes:
    return enc_varint(fno << 3 | 2) + enc_varint(len(payload)) + payload


def cut_line(line: bytes, keep: int, used: set[int]) -> bytes:
    out, n = bytearray(), 0
    for fno, wt, val, raw in fields(line):
        if fno == 4 and wt == 2:
            n += 1
            if n > keep:
                continue
            for f2, w2, v2, _ in fields(val):
                if f2 == 1 and w2 == 0:
                    used.add(v2)
        out += raw
    return bytes(out)


def cut_plane(plane: bytes, keep: int) -> bytes:
    used: set[int] = set()
    parts = []
    for fno, wt, val, raw in fields(plane):
        if fno == 3 and wt == 2:
            parts.append((3, ld(3, cut_line(val, keep, used))))
        else:
            parts.append((fno, raw, val, wt))
    out = bytearray()
    for p in parts:
        if p[0] == 4 and p[3] == 2:  # event_metadata map entry: key is field 1
            key = next((v for f, w, v, _ in fields(p[2]) if f == 1 and w == 0), None)
            if key not in used:
                continue
        out += p[1]
    return bytes(out)


def main(argv: list[str]) -> int:
    keep = int(argv[3]) if len(argv) > 3 else 1500
    with open(argv[1], "rb") as f:
        space = f.read()
    out = bytearray()
    for fno, wt, val, raw in fields(space):
        out += ld(1, cut_plane(val, keep)) if (fno == 1 and wt == 2) else raw
    with open(argv[2], "wb") as f:
        f.write(out)
    print(f"{len(space)} -> {len(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
