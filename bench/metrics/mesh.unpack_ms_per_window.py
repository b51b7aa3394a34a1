"""Host time to unpack one window's per-key counts, per call: the
program's ``mesh.unpack`` region in ``A2AMeshProgram.__call__`` (the loop
over every key that builds ``[(key, count), ...]``, after the fetch),
mean over the calls in the window."""
from bench import spans


def read(r):
    got = spans.in_window(r, "mesh.unpack")
    return sum(t1 - t0 for _, t0, t1, _ in got) / len(got) * 1e3 \
        if got else None
