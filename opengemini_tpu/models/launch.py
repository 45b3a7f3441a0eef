"""Launch groups: the batches of one statement that froze to the same
geometry are reduced by ONE compiled program and fetched as ONE result.

A dashboard panel asks the same aggregate of five fields of the same
rows; its five grids are the same shape, and so are a fleet statement's
five bucket matrices.  Launched field by field that is five dispatches
and five blocking fetches of five or six arrays each — five round trips
to the chip for a tenth of a millisecond of device work.  Here a batch
describes each kernel it needs as an Item (the kernel, the matrices it
reads, where its statistics go); dispatch() groups the items by what
can be observed — program, kernel, the arguments' shapes, dtypes and
placement — and calls one program a group:

  - the program takes the group's matrices as a tuple of per-field
    argument tuples (a pytree: no host-side stack of 17 MB matrices) and
    runs the per-field kernel there is (grid `basic`/`ssd`/`selectors`,
    bucket `basic`/`selectors`) on each, so every statistic is the
    number the kernel gives alone;
  - it returns them packed: one float array (fields, statistics, ...)
    and at most one integer array, so a launch is one wait and one or
    two device-to-host copies, whatever the number of fields;
  - every statistic the kernel computes comes back, whichever the
    statement's aggregates read: one compiled variant a (kernel,
    fields, shape, dtype), no per-aggregate variants.

A statement of one field, `run()` on a lone batch (the cluster's
partials) and the sliced scan's `prefetch()` are groups of one through
the same code.  dispatch() returns before the device is done; a group's
Flight lands (fetch, unpack, hand each item its statistics) when the
first of its batches needs a number, or at once under run().
"""

from __future__ import annotations

import functools

import numpy as np

from opengemini_tpu.utils import devobs
from opengemini_tpu.utils.querytracker import GLOBAL as TRACKER


class Item:
    """One kernel over one batch's matrices.  `program` names the kernel
    family in the compile inventory and the device trace (`grid_basic`,
    `bucket_selectors`); `kernel(*args)` is the traceable per-field
    function returning {statistic: array}; `args` is only what it reads,
    host or device arrays, let go of once dispatched; `sink(stats)`
    takes the statistics as host arrays."""

    __slots__ = ("program", "kernel", "args", "sink", "flight")

    def __init__(self, program, kernel, args, sink):
        self.program = program
        self.kernel = kernel
        self.args = tuple(args)
        self.sink = sink
        self.flight = None  # set once dispatched

    def key(self):
        """What makes two items one launch: the same kernel over
        arguments of the same shapes, dtypes and placement."""
        return (self.program, self.kernel,
                tuple((a.shape, str(a.dtype)) for a in self.args),
                tuple(getattr(a, "sharding", None) for a in self.args))


class Flight:
    """A dispatched launch whose result is still on the device.  land()
    fetches it once (one `device_fetch` span) and delivers it."""

    __slots__ = ("_out", "_deliver")

    def __init__(self, out, deliver):
        self._out = out
        self._deliver = deliver

    def land(self) -> None:
        if self._deliver is None:
            return
        out, deliver = self._out, self._deliver
        self._out = self._deliver = None
        deliver(devobs.fetch_tree(out))


def dispatch(items) -> None:
    """Group `items` and call one program a group; every item leaves with
    its `flight`.  Items already flying are left alone."""
    groups: dict[tuple, list[Item]] = {}
    for it in items:
        if it.flight is None:
            groups.setdefault(it.key(), []).append(it)
    for (program, kernel, sig, _placement), group in groups.items():
        TRACKER.check()  # KILL QUERY cancellation point, once a launch
        fn, fnames, inames = _program(program, kernel, len(group), sig)
        devobs.note_use(program, (len(group), sig))
        out = devobs.launch(fn, (tuple(it.args for it in group),),
                            program=program,
                            xfer_site=program.partition("_")[0] + "-launch")
        flight = Flight(out, functools.partial(
            _deliver, group, fnames, inames))
        for it in group:
            it.flight = flight
            it.args = ()


def pending(items: dict, kinds, make) -> list:
    """A batch's items of these kernel kinds that are not in flight yet;
    `items` is the batch's own {kind: Item}, filled by `make(kind)` the
    first time a kind is asked for."""
    out = []
    for kind in kinds:
        it = items.get(kind)
        if it is None:
            it = items[kind] = make(kind)
        if it.flight is None:
            out.append(it)
    return out


def settle(items: dict, kinds, make) -> None:
    """What a lone run() does: whatever no launch group brought yet is
    launched now, as a group of one, and every flight of these kinds
    landed."""
    dispatch(pending(items, kinds, make))
    for kind in kinds:
        items[kind].flight.land()


def run(items) -> None:
    """dispatch() every group, then land them all: the device works on
    the second group while the first is fetched."""
    items = list(items)
    dispatch(items)
    for it in items:
        it.flight.land()


def _deliver(group, fnames, inames, got) -> None:
    floats, ints = got
    for j, it in enumerate(group):
        stats = {n: floats[j, i] for i, n in enumerate(fnames)}
        stats.update((n, ints[j, i]) for i, n in enumerate(inames))
        it.sink(stats)


@functools.lru_cache(maxsize=256)
def _program(program: str, kernel, fields: int, sig: tuple):
    """(compiled program, float statistic names, integer statistic names)
    for `fields` argument tuples of signature `sig`.  The program is
    named after its family, so the device trace and the compile cache say
    `jit_grid_basic` / `jit_bucket_basic`."""
    import jax
    import jax.numpy as jnp

    devobs.note_compile(program, (fields, sig))
    avals = [jax.ShapeDtypeStruct(shape, np.dtype(dt)) for shape, dt in sig]
    names = sorted(jax.eval_shape(kernel, *avals).items())
    fnames = tuple(n for n, a in names
                   if jnp.issubdtype(a.dtype, jnp.inexact))
    inames = tuple(n for n, a in names if n not in fnames)

    def packed(args):
        outs = [kernel(*a) for a in args]
        return tuple(
            jnp.stack([jnp.stack([o[n] for n in part]) for o in outs])
            if part else None
            for part in (fnames, inames))

    packed.__name__ = packed.__qualname__ = program
    return jax.jit(packed), fnames, inames
