"""Picklable nodes and child targets for the procs-backend tests.

Spawned vertex processes unpickle their nodes by *importing the defining
module* — so everything a procs test ships to a child lives here, in a
module with no test-only imports (no hypothesis, no pytest, no jax):
a child must be able to import it cold, cheaply.
"""
from __future__ import annotations

import time


# -- plain svc functions ------------------------------------------------------
def f(x):
    return x * 3 + 1


def g(x):
    return x - 7


def sq(x):
    return x * x


def fb_step(x):
    return x * 2 + 1


def fb_pred(x):
    return x < 64


def fb_ref(x):
    x = fb_step(x)
    while fb_pred(x):
        x = fb_step(x)
    return x


def drop_odd(x):
    from repro.core import GO_ON
    return x if x % 2 == 0 else GO_ON


def boom_on_seven(x):
    if x == 7:
        raise ValueError("boom at 7")
    return x


def sleepy(x):
    time.sleep(60.0)  # a wedged worker: only the run timeout can save us
    return x


def fb_slow_step(x):
    time.sleep(0.0005)  # slower than the dispatch: its worker ring fills
    return fb_step(x)


def slow_f(x):
    time.sleep(0.0005)
    return f(x)


def big_payload(x):
    return ("#" * 5000, x)  # forces the shm ring's spill side-channel


# -- monitor-test nodes: a synthetic skewed pipeline (one stage 10x slower) --
def fast_stage(x):
    time.sleep(0.0002)
    return x + 1


def slow_stage(x):
    time.sleep(0.002)  # the 10x-slower stage the analyzer must name
    return x * 2


# -- all-to-all / stream_ops nodes (spawned children re-import these) --------
def mod3(x):
    return x % 3


def mod5(x):
    return x % 5


def mod7(x):
    return x % 7


def double(x):
    return x * 2


def second(t):
    return t[1]


def mod2int(x):
    # array-polymorphic float key: floor-div keeps it traceable on the
    # mesh (host key 0.0 and mesh key 0 hash equal, so dicts agree)
    return (x // 1) % 2


def keep_larger(a, b):
    return a if a >= b else b


def emit_twice(x):
    from repro.core import EmitMany
    return EmitMany([x, x])


def emit_pair_batch(x):
    # one KeyBatch per input: a single wire message whose items a plain
    # downstream stage must still see individually (test_oocore)
    from repro.core import KeyBatch
    return KeyBatch([(x, 0.5 * x), (x, 0.5 * x + 1)])


class Dedup:
    """Stateful per-partition worker: emits each value once (GO_ON after),
    used to pin that partition_by instantiates worker classes fresh."""

    def __init__(self):
        self.seen = set()

    def __call__(self, x):
        from repro.core import GO_ON
        if x in self.seen:
            return GO_ON
        self.seen.add(x)
        return x


class TagPartition:
    """Right-row worker that stamps its partition index on every item —
    lets tests observe which partition serviced which key."""

    def __init__(self, j):
        self.j = j

    def __call__(self, x):
        return (self.j, x)


# -- ff_node-style emitter/collector -----------------------------------------
class AddTagEmitter:
    """Emitter node: runs inside the dispatch arbiter's process."""

    def svc_init(self):
        pass

    def svc_end(self):
        pass

    def svc(self, task):
        return task + 100


class NegateCollector:
    """Collector node: runs inside the merge arbiter's process."""

    def svc_init(self):
        pass

    def svc_end(self):
        pass

    def svc(self, task):
        return -task


def np_double(x):
    # numpy payload node: lazy import, so children that never service an
    # array keep their cold import cheap (and repro.core stays numpy-free)
    import numpy as np
    return np.asarray(x) * 2.0


# -- out-of-core aggregation nodes (test_oocore; spawned children) -----------
def mod10_pair(kv):
    return kv[0] % 10


def add_val(acc, kv):
    return acc + kv[1]


def add2(a, b):
    return a + b


def row_key(row):
    return row[0]


def row_stats(acc, row):
    """Seeded fold over (key, value) rows -> (count, total)."""
    return (acc[0] + 1, acc[1] + row[1])


def merge_stats(a, b):
    """Combine two (count, total) partials of one key."""
    return (a[0] + b[0], a[1] + b[1])


class RangeRows:
    """Synthetic columnar dataset: ``reader(lo, hi)`` -> list of
    ``(key, value)`` rows, deterministic from the row index alone, so
    every shard (and every process) reads the same dataset with no file.
    ``nrows``/``nkeys`` make it a drop-in for ``shard_source``."""

    def __init__(self, nrows, nkeys):
        self.nrows = nrows
        self.nkeys = nkeys

    def __call__(self, lo, hi):
        nk = self.nkeys
        return [((i * 2654435761) % nk, float(i % 97)) for i in range(lo, hi)]


# -- child targets for test_shm ----------------------------------------------
def echo_child(inbound, outbound):
    """Pop until EOS; report whether each sentinel kept identity."""
    from repro.core import EOS, GO_ON
    while True:
        item = inbound.pop_wait(timeout=30)
        if item is EOS:
            outbound.push_wait(("eos-is-eos", True), timeout=30)
            return
        if item is GO_ON:
            outbound.push_wait(("go-on-is-go-on", True), timeout=30)
            continue
        outbound.push_wait(item, timeout=30)


def bump_child(board):
    board.add(1, 5)  # slot 1 is this process's single-writer counter


def set_flag_child(flag):
    flag.set()


def np_sum_child(inbound, outbound):
    """Pop numpy arrays until EOS; reply (dtype str, shape, scalar sum)
    per array so the parent can assert zero-copy decode fidelity."""
    from repro.core import EOS
    while True:
        item = inbound.pop_wait(timeout=30)
        if item is EOS:
            return
        outbound.push_wait(
            (item.dtype.str, item.shape, float(item.sum())), timeout=30)
