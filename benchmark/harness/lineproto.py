"""Line protocol at the speed of a numpy fancy assignment.

Copied from chip_smoke.py (`TickWriter`, `_digits`, PR 21) and generalised
from "one line per series of one tick" to any list of lines, so that one
request can carry many ticks of a block of series (the fast set-up load)
or 2.5 ticks of every host (TSBS's loader).  The original stays where it
is; this copy is the yardstick's (PERF.md, open questions)."""

from __future__ import annotations

import numpy as np

TS_WIDTH = 19          # ns since the epoch, 2001..2262


def digits(values: np.ndarray, width: int) -> np.ndarray:
    """(n,) non-negative ints -> (n, width) ASCII digits, zero-padded."""
    powers = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return ((values[:, None] // powers) % 10 + 48).astype(np.uint8)


class LineTemplate:
    """Lines `<prefix> f0=<slot>,f1=<slot>.. <ts>\\n` as one byte buffer
    whose fixed-width slots are overwritten per request."""

    def __init__(self, prefixes: list[bytes], fields: tuple[str, ...],
                 width: int):
        slot = b"0" * width
        tail = b",".join(f.encode() + b"=" + slot for f in fields)
        lines = [p + b" " + tail + b" " + b"0" * TS_WIDTH + b"\n"
                 for p in prefixes]
        self.buf = np.frombuffer(b"".join(lines), np.uint8).copy()
        lens = np.array([len(ln) for ln in lines], np.int64)
        starts = np.concatenate(([0], np.cumsum(lens[:-1])))
        plen = np.array([len(p) for p in prefixes], np.int64) + 1
        in_tail = np.array([sum(len(g) + 1 + width + 1 for g in fields[:i])
                            + len(f) + 1 for i, f in enumerate(fields)])
        base = (starts + plen)[:, None] + in_tail[None, :]
        self.val_pos = base[:, :, None] + np.arange(width)[None, None, :]
        ts0 = starts + plen + len(tail) + 1
        self.ts_pos = ts0[:, None] + np.arange(TS_WIDTH)[None, :]
        self.lines = len(lines)

    def fill(self, slots: np.ndarray, ts_ns: np.ndarray) -> bytes:
        """slots: (lines, fields, width) ASCII bytes; ts_ns: (lines,)."""
        self.buf[self.val_pos] = slots
        self.buf[self.ts_pos] = digits(np.asarray(ts_ns, np.int64), TS_WIDTH)
        return self.buf.tobytes()
