"""The vertex protocol on both host runners.

``repro.core.vertex`` is run by the threads runner (``graph.Graph``) and
the procs runner (``procgraph.ProcGraph``).  Each case here drives one
part of the protocol that each backend's own tests reach on only one of
them, on both: the wrap-around stash (loop quiescence while a worker
ring is full), a worksteal backlog flushed before EOS, and a worker's
error surfacing without a hang.  Nodes live in ``tests/_procs_nodes.py``
so spawned vertices can import them.
"""
import threading

import pytest

import _procs_nodes as N
from repro.core import Farm, Feedback, lower


def _bounded(run, seconds=60.0):
    """Run ``run()`` on a thread and fail if it has not ended within
    ``seconds``: a protocol fault shows as a hang, not an exception."""
    box = {}

    def go():
        try:
            box["out"] = run()
        except BaseException as e:  # re-raised on the test's thread
            box["err"] = e

    t = threading.Thread(target=go, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), "the graph hung"
    if "err" in box:
        raise box["err"]
    return box["out"]


def _lower(skel, backend, **opts):
    if backend == "procs":
        opts["timeout"] = 60.0
    return lower(skel, backend, **opts)


def _loop_with_a_full_worker_ring(backend):
    # one slow worker behind rings of 3 slots: the dispatch arbiter blocks
    # on the worker ring while looped-back tokens arrive, stashes them,
    # and must still reach quiescence with every item out
    xs = list(range(48))
    prog = _lower(Feedback(N.fb_slow_step, N.fb_pred, nworkers=1), backend,
                  capacity=2)
    assert _bounded(lambda: prog(xs)) == [N.fb_ref(x) for x in xs]


def _worksteal_backlog_flushed_before_eos(backend):
    # 240 items reach the arbiter long before three slow workers finish:
    # the policy holds a backlog when the upstream EOS arrives, and every
    # held token must go out ahead of the EOS
    xs = list(range(240))
    skel = Farm(N.slow_f, 3, ordered=True, scheduling="worksteal")
    assert _bounded(lambda: _lower(skel, backend)(xs)) == [N.f(x)
                                                           for x in xs]
    assert skel.stats.tasks_emitted == skel.stats.tasks_collected == len(xs)


def _worker_error_surfaces(backend):
    # the worker dies at item 7 with its inbound ring full behind it: the
    # dispatch arbiter must give up on the push, not wait forever
    skel = Farm(N.boom_on_seven, 1, ordered=True)
    with pytest.raises(ValueError, match="boom at 7"):
        _bounded(lambda: _lower(skel, backend, capacity=4)(range(500)))


CASES = {
    "loop_stash": _loop_with_a_full_worker_ring,
    "worksteal_flush": _worksteal_backlog_flushed_before_eos,
    "worker_error": _worker_error_surfaces,
}


@pytest.mark.parametrize("backend", ["threads", "procs"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_protocol_parity(case, backend):
    CASES[case](backend)
