"""Import hygiene for the host runtime (PR-4's PEP 562 lazy loading).

The procs backend spawns one process per vertex and every child imports
``repro.core`` cold; pulling jax (seconds of XLA start-up) into that path
would silently multiply spawn cost by every vertex in every run.  These
tests pin, via a *subprocess* (the parent test process has long since
imported jax), that the host-side surface — ``repro.core`` and the whole
all-to-all/stream_ops layer — never imports jax as a side effect."""
import os
import subprocess
import sys

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_isolated(code: str) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_core_import_stays_jax_free():
    _run_isolated(
        "import sys; import repro.core; "
        "assert 'jax' not in sys.modules, 'repro.core imported jax'")


def test_a2a_and_stream_ops_import_stays_jax_free():
    """The new subsystem must keep the same discipline: the mesh program
    class is importable, but jax loads only when it is instantiated."""
    _run_isolated(
        "import sys; "
        "from repro.core import A2AMeshProgram, AllToAll, reduce_by_key, "
        "partition_by, window, KeyAffinity; "
        "import repro.core.a2a, repro.core.stream_ops; "
        "assert 'jax' not in sys.modules, 'a2a/stream_ops imported jax'")


def test_oocore_stays_jax_free():
    """The out-of-core layer ships to every spawned vertex (SpillFold
    partitions, combining readers): importing it, building a budgeted
    shard_reduce, and running a spill-forced fold must never load jax."""
    _run_isolated(
        "import sys\n"
        "from repro.core import (KeyBatch, MemoryBudget, SpillFold, "
        "shard_reduce, shard_source, rekey_reduce)\n"
        "import repro.core.oocore\n"
        "def key(r): return r[0]\n"
        "sf = SpillFold(abs, max, budget=MemoryBudget(400))\n"
        "for x in range(-200, 200): sf.svc(x)\n"
        "out = [kv for chunk in sf.svc_eos() for kv in chunk]\n"
        "assert len(out) == 201 and sf._dir is None, (len(out), sf._dir)\n"
        "assert 'jax' not in sys.modules, 'oocore imported jax'")


def test_autotune_stays_jax_free():
    """The self-tuning loop is host-side: profiling a pilot, retuning the
    IR, and replaying on threads must never load jax (plan_mesh is the
    only device-aware entry point and imports it lazily)."""
    _run_isolated(
        "import sys\n"
        "from repro.core import (Pipeline, Stage, TunedProgram, lower, "
        "profile, retune)\n"
        "import repro.core.autotune\n"
        "def f(x): return x + 1\n"
        "def g(x): return x * 2\n"
        "skel = Pipeline(Stage(f, grain=10000), Stage(g, grain=10000))\n"
        "prof = profile(skel, range(64))\n"
        "tuned = lower(retune(skel, prof), 'threads', fuse=False)\n"
        "assert tuned(range(10)) == [(x + 1) * 2 for x in range(10)]\n"
        "tp = lower(skel, 'threads', tune=True, tune_pilot=16)\n"
        "assert tp(range(40)) == [(x + 1) * 2 for x in range(40)]\n"
        "assert 'jax' not in sys.modules, 'autotune imported jax'")


def test_obs_stays_jax_free():
    """The observability layer rides in every spawned vertex (child-side
    VertexTracer construction) and in the eager ``repro.core`` surface:
    importing it, tracing a lowered run, and exporting Chrome JSON must
    never load jax."""
    _run_isolated(
        "import sys\n"
        "import repro.core.obs\n"
        "from repro.core import Farm, MetricsRegistry, Tracer, lower\n"
        "def f(x): return x + 1\n"
        "prog = lower(Farm(f, nworkers=2), 'threads', trace=True, "
        "metrics=True)\n"
        "out = prog(range(50))\n"
        "assert sorted(out) == list(range(1, 51)), out\n"
        "doc = prog.last_trace.to_chrome_json()\n"
        "assert doc['traceEvents'], 'empty trace'\n"
        "assert prog.last_report.farms, 'no farm stats in report'\n"
        "assert 'jax' not in sys.modules, 'obs/tracing imported jax'")


def test_ir_construction_stays_jax_free():
    """Building and thread-lowering a keyed reduction — the exact work a
    spawned vertex's unpickle path does — must not touch jax either."""
    _run_isolated(
        "import sys\n"
        "from repro.core import lower, reduce_by_key\n"
        "def mod(x): return x % 3\n"
        "out = dict(lower(reduce_by_key(mod, 'sum', nright=2), "
        "'threads')(range(10)))\n"
        "assert out == {0: 18, 1: 12, 2: 15}, out\n"
        "assert 'jax' not in sys.modules, 'thread lowering imported jax'")


def test_monitor_stays_jax_free():
    """The live-monitoring layer is eagerly imported by ``repro.core``
    and its sampler thread rides inside monitored host runs: importing
    it, monitoring a threads run, analyzing the timeline and rendering
    the report must never load jax."""
    _run_isolated(
        "import sys\n"
        "import repro.core.monitor\n"
        "from repro.core import Farm, Monitor, Pipeline, analyze, lower\n"
        "def f(x): return x + 1\n"
        "mon = Monitor(interval_s=0.001)\n"
        "prog = lower(Pipeline(f, Farm(f, nworkers=2)), 'threads', "
        "monitor=mon)\n"
        "out = prog(range(80))\n"
        "assert sorted(out) == [x + 2 for x in range(80)], out[:5]\n"
        "assert mon.timeline.frames(), 'monitor sampled nothing'\n"
        "rep = analyze(mon.timeline)\n"
        "assert rep.render(), 'empty report render'\n"
        "assert 'jax' not in sys.modules, 'monitor imported jax'")


def test_kernels_import_opens_no_device():
    """Importing the kernels (and their references) builds numpy tables
    only: no JAX backend starts, so a process that imports them does not
    take the accelerator."""
    _run_isolated(
        "from jax._src import xla_bridge\n"
        "import repro.kernels.ops, repro.kernels.ref\n"
        "assert not xla_bridge.backends_are_initialized(), "
        "'importing repro.kernels started a JAX backend'")
