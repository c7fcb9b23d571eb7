"""``narrow_lanes.count``: percent of the lanes the count program pads
its tiles to (as ``lane_util.count`` counts them) whose contributions
reach the 64-bit counts through int32 partials, a batch or a vertex
tile at a time: all of a program's lanes where it was launched with
``narrow=True``, none where ``narrow=False``. None where no launched
count program has the ``narrow`` argument."""
import numpy as np

PROGRAM = "run_fused_pallas_program"


def read(run):
    narrow = lanes = 0
    for name, args, kw in run.programs:
        if name != PROGRAM or "narrow" not in kw:
            continue
        ktiles = np.asarray(args[1])  # (batches, 16, 2)
        n = ktiles.shape[0] * ktiles.shape[1] * int(kw["tile_cap"])
        for tiles, cap in zip(args[2], kw["vertex_caps"]):
            n += np.asarray(tiles).shape[0] * int(cap)
        lanes += n
        narrow += n if kw["narrow"] else 0
    return 100.0 * narrow / lanes if lanes else None
