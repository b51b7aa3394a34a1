"""Quickstart: the three layers of FastFlow-JAX in ~100 lines.

  1. the skeleton IR: ONE declarative expression, executed on THREE
     backends — the host thread/SPSC graph, the GIL-escaping process
     graph over shared-memory rings, and a single shard_map mesh program
     (no host hop between stages); plus the threads backend's pluggable
     scheduling policies (Farm(scheduling=...)), the grain-aware
     fusion pass (lower(..., fuse=...)), the all-to-all keyed
     shuffle (reduce_by_key — §1d), and its out-of-core form
     (budget= spill-to-disk folds — §1f), and the self-tuning loop
     (profile a pilot slice, retune the IR from the measurements,
     replay on any backend — §1g);
  2. the paper's application: Smith-Waterman database search through an
     ordered farm;
  3. the LM framework: one reduced-config train step + one decode step.

Run:  PYTHONPATH=src python examples/quickstart.py

Structure note: the procs backend spawns vertex processes, and spawn
re-imports this script in every child — so the worker functions live at
module level (picklable by name), the heavy imports live inside main(),
and everything executable is behind ``if __name__ == "__main__"``.
"""


# -- picklable nodes for the procs backend (children import these by name) ----
def _sq(x):
    return x * x


def _dbl(x):
    # array node: x is a numpy array, so `* 2.0` needs no import here —
    # the child that services it gets numpy when it unpickles the payload
    return x * 2.0


def _inc(x):
    return x + 1


def _mod4(x):
    # a shuffle key: array-polymorphic (x % 4 works on a jnp column too),
    # so the SAME key function routes on all three backends
    return x % 4


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ARCHS
    from repro.core import CostModel, Farm, Pipeline, Stage, lower
    from repro.kernels import ops
    from repro.launch.steps import make_train_step
    from repro.models import init_cache, init_params, decode_step
    from repro.optim import adamw_init

    # -- 1. one skeleton, two backends ---------------------------------------
    # Pipeline(Farm(f), Farm(g)) is pure data; lower() picks the runtime.
    skel = Pipeline(Farm(_sq, 4, ordered=True), Farm(_inc, 4, ordered=True))
    on_threads = lower(skel, "threads")(range(10))  # threads + SPSC rings
    on_mesh = lower(skel, "mesh")(range(10))        # ONE shard_map: fused
    print("threads:", on_threads)
    print("mesh:   ", on_mesh)
    assert on_threads == on_mesh

    # -- 1b. scheduling policies + grain-aware fusion (threads backend) ------
    # Farm(scheduling=) takes a registry name — "rr" | "ondemand" |
    # "worksteal" | "costmodel" — or a repro.core.sched.Scheduler instance;
    # placement never changes ordered-farm output, only who services what.
    stolen = lower(Farm(_sq, 4, ordered=True,
                        scheduling="worksteal"), "threads")(range(10))
    priced = lower(Farm(_sq, 4, ordered=True,
                        scheduling=CostModel()), "threads")(range(10))
    assert stolen == priced == [_sq(x) for x in range(10)]
    print("worksteal == costmodel:", stolen)

    # Stages declaring a fine grain= (µs of work per item, threads reading)
    # fuse into ONE vertex when the grain is below the calibrated hand-off
    # cost — fewer threads, fewer ring hops, identical output.
    fine = Pipeline(Stage(_inc, grain=1), Stage(_sq, grain=1))
    fused = lower(fine, "threads", fuse="auto", fuse_threshold_us=1e9)
    unfused = lower(fine, "threads", fuse=False)
    assert fused(range(8)) == unfused(range(8))
    print("fusion: vertices", len(unfused.to_graph(list(range(8))).vertices),
          "->", len(fused.to_graph(list(range(8))).vertices))

    # -- 1c. the SAME skeleton on the procs backend (GIL escape) -------------
    # lower(skel, "procs") spawns one process per vertex and replaces every
    # edge with a shared-memory SPSC ring (cache-line-separated head/tail —
    # the paper's FastForward layout, finally observable without the GIL).
    # Identical ordered output, but a farm of pure-Python svc functions now
    # actually scales with cores; nodes must be picklable (module-level
    # functions like _sq/_inc, not lambdas).
    on_procs = lower(skel, "procs")(range(10))
    print("procs:  ", on_procs)
    assert on_procs == on_threads == on_mesh

    # -- 1d. keyed shuffle: ONE reduce_by_key, THREE backends ----------------
    # reduce_by_key(by, fold) rewrites to the AllToAll building block — an
    # N×M matrix of SPSC edges on the host backends (each left vertex owns
    # one ring per right vertex: single-writer, no arbiter between the
    # layers), and ONE shard_map keyed-exchange + segment-reduction program
    # on the mesh (named fold + static nkeys make it traceable).  Output is
    # unordered (key, fold) pairs — compare as dicts.
    from repro.core import reduce_by_key
    rbk = reduce_by_key(_mod4, "sum", nleft=2, nright=2, nkeys=4)
    by_threads = dict(lower(rbk, "threads")(range(32)))
    by_procs = dict(lower(rbk, "procs")(range(32)))
    by_mesh = dict(lower(rbk, "mesh")(range(32)))
    assert by_threads == by_procs == by_mesh
    print("reduce_by_key (threads == procs == mesh):", by_threads)

    # -- 1e. zero-copy + batched lowering options (procs backend) ------------
    # For array streams, size the ring slots to the payload (slot_size=)
    # and numpy arrays travel as typed zero-copy slots: one aligned memcpy
    # into shared memory per side instead of a pickle round-trip (pickle
    # stays the fallback for arbitrary objects).  batch= packs several
    # items per slot hand-off (an int, or "grain" to read each stage's
    # declared grain), and spawned vertices come from a reusable process
    # pool — a second lower(...) run pays no spawn cost (pool_stats()
    # shows the reuse; opt out per-program with pool=False).
    from repro.core import pool_stats
    arrs = [np.full((1024,), float(i), np.float32) for i in range(12)]
    zc = lower(Farm(_dbl, 2, ordered=True), "procs",
               slot_size=8192, zero_copy=True, batch=4)(arrs)
    assert all(np.array_equal(o, a * 2.0) for o, a in zip(zc, arrs))
    print("zero-copy procs pool:", pool_stats())

    # -- 1f. bounded-memory aggregation (the out-of-core layer) --------------
    # budget= bounds each partition's hot fold state in BYTES: when a
    # partition's dict outgrows it, the coldest keys spill to a sorted
    # on-disk run and the EOS flush merges runs back — identical results,
    # bounded memory, telemetry on skel.stats.  The scatter also gains
    # byte-driven backpressure (stalls intake while the aggregate hot
    # state sits over the budget's high-water mark).  For datasets too
    # big to materialise at all, shard_reduce() composes sharded
    # combining readers with spill-backed partitions — see
    # examples/parquet_aggregation.py for the full walkthrough.
    budgeted = reduce_by_key(_mod4, "sum", nleft=2, nright=2, budget=100)
    by_budget = dict(lower(budgeted, "threads")(range(32)))
    assert by_budget == by_threads
    print(f"budgeted reduce_by_key: same result, spills="
          f"{budgeted.stats.spills} spill_bytes={budgeted.stats.spill_bytes}")
    assert budgeted.stats.spills > 0  # the 100-byte budget forced runs

    # -- 1g. self-tuning: profile -> retune -> replay ------------------------
    # Declared knobs lie (here: grain=10000 on sub-µs stages, so the
    # static lowering never fuses).  profile() runs a pilot slice through
    # an instrumented threads lowering and records per-stage service
    # times, queue high-water marks, and the calibrated hand-off cost;
    # retune() is a pure IR rewrite from those measurements — measured
    # grains, fusion at the measured threshold, rate-ratio ring sizes,
    # micro-batched survivors — and never changes results.  The same
    # profile (it is JSON: prof.save/Profile.load) retunes the procs
    # lowering too; service times are a property of the node functions.
    from repro.core import profile, retune
    misgrained = Pipeline(Stage(_inc, grain=10000), Stage(_sq, grain=10000))
    prof = profile(misgrained, range(200))          # the pilot slice
    tuned = retune(misgrained, prof)                # the rewrite
    want = [_sq(_inc(x)) for x in range(50)]
    assert lower(tuned, "threads", fuse=False)(range(50)) == want
    assert lower(tuned, "procs", fuse=False)(range(50)) == want
    print(f"retune: handoff={prof.handoff_us:.2f}us, "
          f"{len(misgrained.stages)} stages -> "
          f"{len(tuned.stages) if hasattr(tuned, 'stages') else 1}")
    # or let the runtime do both phases: lower(..., tune=True) profiles a
    # pilot off the front of the first stream, then replays the rest
    # (and every later call) through the tuned program.
    tp = lower(misgrained, "threads", tune=True, tune_pilot=64)
    assert tp(range(200)) == [_sq(_inc(x)) for x in range(200)]

    # -- 1h. observability: trace a run, read the RunReport ------------------
    # trace= hands every vertex a sampled, bounded event buffer (svc
    # spans, push-wait stalls, steals, spills, EOS markers) and metrics=
    # folds the farm boards, queue high-water marks and pool stats into
    # one RunReport; both knobs work on all three backends, and with
    # them OFF a vertex carries ``tracer = None`` and never enters
    # repro.core.obs at all.  The export is Chrome trace-event JSON —
    # drop the file on https://ui.perfetto.dev (or chrome://tracing) to
    # see one swim-lane per vertex.
    import os
    import tempfile
    traced = lower(skel, "threads", trace=True, metrics=True)
    assert traced(range(10)) == on_threads
    trace_path = os.path.join(tempfile.gettempdir(), "ff_quickstart.json")
    doc = traced.last_trace.to_chrome_json(trace_path)
    print(f"trace: {len(traced.last_trace.lanes)} lanes, "
          f"{len(doc['traceEvents'])} events -> {trace_path}")
    rep = traced.last_report                 # JSON-able: rep.save(path)
    farm = rep.farms["ff-farm@0"]            # telemetry keys by IR path
    print(f"report: farm@0 collected={farm['tasks_collected']}, "
          f"queue high-water={max(rep.queues.values())}, "
          f"wall={rep.meta['wall_s'] * 1e3:.1f}ms")
    # the report round-trips into §1g's tuning loop: to_profile() turns
    # live telemetry back into a Profile for retune()/Profile.diff.
    assert any(s.kind == "farm" for s in rep.to_profile().stages)

    # -- 1i. live monitoring: watch a run, name the bottleneck ---------------
    # monitor= attaches a background sampler (a Monitor) to the running
    # graph: every ~2ms it snapshots live queue depths, farm service
    # EWMAs and progress counters into a bounded Timeline — no ring
    # traffic, just racy-benign reads of single-writer state.  Feed the
    # timeline to analyze() and it scores each stage by queueing
    # pressure minus outbound drain, names the dominant bottleneck and
    # recommends which autotune knob (§1g) to turn.  Here the farm is
    # deliberately starved of workers, so its inbound ring backs up.
    import time as _time
    from repro.core import Monitor, analyze
    mon = Monitor(interval_s=0.001)
    skewed = Pipeline(_inc, Farm(lambda x: (_time.sleep(0.001), x)[1],
                                 nworkers=2))
    lower(skewed, "threads", monitor=mon)(range(256))
    report = analyze(mon.timeline)
    print(f"monitor: {len(mon.timeline.frames())} frames -> "
          f"bottleneck={report.stage} [{report.verdict}]")
    assert report.stage == "ff-farm@1", report.stage
    knobs = [r["knob"] for r in report.recommendations]
    print(f"monitor: recommended knobs={knobs}")   # e.g. nworkers first
    # mon.timeline.save(path) persists it; `python -m repro.core.monitor
    # <path>` renders the same analysis top-style in a terminal, and
    # to_chrome_json(path, timeline=mon.timeline) overlays the depth
    # curves as Perfetto counter tracks on §1h's swim-lanes.

    # -- 2. the paper's app: SW database search (host-only payloads) ---------
    rng = np.random.default_rng(0)
    query = jnp.asarray(rng.integers(0, 20, 32), jnp.int32)
    db = [jnp.asarray(rng.integers(0, 20, int(n)), jnp.int32)
          for n in rng.integers(20, 80, 8)]
    sw = Farm(lambda s: float(ops.smith_waterman(query, s, tile=64)), 2,
              ordered=True)
    print("SW scores:", lower(sw, "threads")(db))

    # -- 3. LM framework: one train step + one decode step (reduced config) --
    cfg = ARCHS["mixtral-8x7b"].smoke()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg))
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (2, 32), 0, cfg.vocab_size)}
    params, opt, metrics = step(params, opt, batch)
    print(f"train step: loss={float(metrics['loss']):.3f}")

    cache = init_cache(cfg, batch=2, max_len=16)
    logits, cache = jax.jit(lambda p, b, c, l: decode_step(p, b, c, l, cfg))(
        params, {"tokens": jnp.zeros((2, 1), jnp.int32)}, cache,
        jnp.zeros((2,), jnp.int32))
    print("decode logits:", logits.shape)
    print("quickstart OK")


if __name__ == "__main__":
    main()
