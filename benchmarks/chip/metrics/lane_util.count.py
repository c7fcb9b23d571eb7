"""``lane_util.count``: percent of the lanes the count program pads its
tiles to that hold a real wedge: kernel tiles (batches of ``tile_cap``
lanes) and per-vertex tiles (each in its power-of-two class), as
launched. A count of the plan, the same on every job of a graph."""
import numpy as np

PROGRAM = "run_fused_pallas_program"


def read(run):
    real = lanes = 0
    for name, args, kw in run.programs:
        if name != PROGRAM:
            continue
        ktiles = np.asarray(args[1]).astype(np.int64)  # (batches, 16, 2)
        lanes += ktiles.shape[0] * ktiles.shape[1] * int(kw["tile_cap"])
        real += int((ktiles[..., 1] - ktiles[..., 0]).sum())
        for tiles, cap in zip(args[2], kw["vertex_caps"]):
            t = np.asarray(tiles).astype(np.int64)
            lanes += t.shape[0] * int(cap)
            real += int((t[:, 1] - t[:, 0]).sum())
    return 100.0 * real / lanes if lanes else None
