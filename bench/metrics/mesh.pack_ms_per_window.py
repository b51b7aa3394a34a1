"""Host time to pack one window of bids for the mesh program, per call:
the program's ``mesh.pack`` region in ``A2AMeshProgram.__call__`` (the
list of items, ``np.asarray``, the int32 check, the pre-map and key-range
check, the pad to whole rows a chip), mean over the calls in the window."""
from bench import spans


def read(r):
    got = spans.in_window(r, "mesh.pack")
    return sum(t1 - t0 for _, t0, t1, _ in got) / len(got) * 1e3 \
        if got else None
