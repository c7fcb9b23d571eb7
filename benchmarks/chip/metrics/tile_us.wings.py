"""``tile_us.wings``: device busy microseconds of the wing peel program
per tile it streams, the latency of one step of its tile loop.

Busy time is the union of the device events of the window's
``_peel_wings_device`` launches. Tiles are counted from the launched
program and the reference: every sub-round but the last (which leaves
no edge alive, so subtracts nothing) streams ``ceil(W / tile_cap)``
tiles, ``W`` the sum of the program's ``work2`` argument (each edge's
candidate triples) over the edges the reference peels in it (``step``).
The argument is used only where it equals the reference's own
``triples``, edge for edge. None where the program launched no such
program or span, or passed another ``work2``."""
import numpy as np

from benchmarks.chip import scopes, trace

PROGRAM = "_peel_wings_device"
WORK2 = 10  # position of ``work2`` among the program's arguments


def streamed_tiles(programs: list, ref: dict):
    """Tiles one job's wing program streams, or None without it or where
    the argument at ``WORK2`` is not the per-edge candidate triples."""
    for name, args, kw in programs:
        if name == PROGRAM:
            if len(args) <= WORK2 or "tile_cap" not in kw:
                return None
            work2 = np.asarray(args[WORK2])
            if not np.array_equal(work2, ref["triples"]):
                return None
            per_step = np.bincount(ref["step"], weights=work2,
                                   minlength=ref["rounds"]).astype(np.int64)
            cap = int(kw["tile_cap"])
            return int((-(-per_step[:ref["rounds"] - 1] // cap)).sum())
    return None


def read(run):
    tiles = streamed_tiles(run.programs, run.reference)
    events = scopes.program_events(run)
    if not tiles or not events or not events.get(PROGRAM):
        return None
    busy_ns = sum(e - s for s, e in trace._merge(
        (s, s + d) for _n, s, d in events[PROGRAM]))
    return busy_ns * 1e-3 / (tiles * run.jobs)
