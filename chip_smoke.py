#!/usr/bin/env python3
"""Run the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: device, protein search,
                                      # Phi-3-mini serving, mesh skeleton
    python chip_smoke.py --chips 4    # four chips: the mesh skeletons only

Each phase prints its results on lines of its own, tagged ``[phase]``.
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed; any failure raises and exits
non-zero.  There is no CPU fallback: a process whose JAX finds no TPU
fails before any phase runs.  Everything runs in this one process, which
holds the chip; nothing here starts a child that touches JAX.  Times
printed are from a smoke run, not measurements.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0

# protein database search (paper Sec. 4.2)
SW_GAPS = ((10.0, 2.0, "10-2k"), (5.0, 2.0, "5-2k"))
SW_SUBJECTS = 256          # subjects drawn with Swiss-Prot 57.5 statistics
SW_CHECKED = 3             # seeded subset checked cell by cell per run
SW_FARM_WORKERS = 4
SW_TILE = 512

# serving at published widths
SERVE_ARCH = "phi3-mini-3.8b"
SERVE_REQUESTS = 8
SERVE_MAX_NEW = 16
SERVE_PROMPT = (3, 32)     # prompt lengths, inclusive
SERVE_BATCH = 4
SERVE_MAX_LEN = 256

# skeleton mesh backend
MESH_ITEMS = 4096
MESH_NKEYS = 64


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# -- skeleton nodes: module level, array-polymorphic (the mesh backend
# applies them to whole columns, the threads backend to single items) ---------
def mesh_f(x):
    return x * 3 + 1


def mesh_g(x):
    return x - 7


def fb_step(x):
    return x * 2 + 1


def fb_more(x):
    return x < 4096


def mod_key(x):
    return x % MESH_NKEYS


# -- phases --------------------------------------------------------------------
def phase_device(min_count: int) -> dict:
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found: JAX's devices are "
            f"{first.platform!r} ({len(devices)} of them); this check runs "
            f"on a TPU only and has no CPU fallback")
    if len(devices) < min_count:
        raise SystemExit(f"chip_smoke: needs {min_count} TPU chips, "
                         f"JAX sees {len(devices)}")
    info = {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}
    log("device", f"{info} ids={[d.id for d in devices]}")
    return info


def phase_protein(query_lens, n_subjects: int = SW_SUBJECTS,
                  n_checked: int = SW_CHECKED, tile: int = SW_TILE) -> None:
    """Stream a seeded database through an order-preserving farm of the
    Smith-Waterman kernel for each query length and gap regime; check a
    seeded subset of scores exactly against the cell-by-cell reference."""
    import numpy as np

    from benchmarks.smith_waterman import make_db
    from repro.core import Farm, Pipeline, Stage, lower
    from repro.kernels import ops
    from repro.kernels.ref import sw_numpy

    interpret = ops.resolve_interpret()
    rng = np.random.default_rng(SEED)
    db = make_db(rng, n_subjects)
    cells = sum(len(s) for s in db)
    matrix = ops.BLOSUM50
    codes = {c: i for i, c in enumerate(ops.AA_ALPHABET)}

    def text(seq):
        return "".join(ops.AA_ALPHABET[c] for c in seq)

    def score(a, b):
        return float(matrix[codes[a], codes[b]])

    def whole_score(s):
        # integer substitution scores and gaps: every score is a whole,
        # non-negative number
        if not (s >= 0 and float(s).is_integer()):
            raise AssertionError(f"score {s} is not a whole number >= 0")
        return s

    log("protein", f"{n_subjects} subjects, {cells} residues, "
                   f"interpret={interpret}")
    for gap_open, gap_extend, tag in SW_GAPS:
        for q_len in query_lens:
            query = rng.integers(0, 20, q_len).astype(np.int32)
            hlo = ops.smith_waterman_padded.lower(
                query, ops.pad_subject(db[0], tile, matrix.shape[0]), matrix,
                gap_open=gap_open, gap_extend=gap_extend, tile=tile,
                interpret=interpret).as_text()
            if "tpu_custom_call" not in hlo:
                raise AssertionError("the Smith-Waterman kernel did not "
                                     "lower to a Mosaic custom call")

            def align(subject, query=query, gap_open=gap_open,
                      gap_extend=gap_extend):
                return float(ops.smith_waterman(
                    query, subject, gap_open=gap_open,
                    gap_extend=gap_extend, tile=tile))

            search = lower(Pipeline(
                Farm(align, SW_FARM_WORKERS, ordered=True),
                Stage(whole_score)), "threads")
            t0 = time.perf_counter()
            scores = search(db)
            wall = time.perf_counter() - t0
            if len(scores) != len(db):
                raise AssertionError(f"{len(scores)} scores for "
                                     f"{len(db)} subjects")
            picks = sorted(int(i) for i in
                           rng.choice(len(db), n_checked, replace=False))
            for i in picks:
                want = sw_numpy(text(query), text(db[i]), score,
                                gap_open, gap_extend)
                if scores[i] != want:
                    raise AssertionError(
                        f"gaps {tag} q={q_len} subject {i}: kernel "
                        f"{scores[i]} != reference {want}")
            log("protein", f"gaps={tag} q_len={q_len} subjects={len(db)} "
                           f"best={max(scores):.0f} checked={picks} exact; "
                           f"wall {wall:.3f}s incl. compile "
                           f"(smoke run, not a measurement)")


def phase_serve(cfg, max_batch: int = SERVE_BATCH,
                max_len: int = SERVE_MAX_LEN) -> None:
    """Serve seeded requests through ``ServeEngine``; every decode step's
    logits must be finite and every request must come back in tag order
    with ``SERVE_MAX_NEW`` tokens below the vocabulary size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import Request, ServeEngine

    t0 = time.perf_counter()
    eng = ServeEngine(cfg, max_batch=max_batch, max_len=max_len, seed=SEED)
    jax.block_until_ready(eng.params)
    t_params = time.perf_counter() - t0
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(eng.params))

    step, finite, step_s = eng._step, [], []

    def checked_step(params, batch, cache, positions):
        t = time.perf_counter()
        nxt, logits, cache = step(params, batch, cache, positions)
        finite.append(bool(jnp.isfinite(logits).all()))
        step_s.append(time.perf_counter() - t)
        return nxt, logits, cache

    eng._step = checked_step
    rng = np.random.default_rng(SEED)
    lo, hi = SERVE_PROMPT
    for rid in range(SERVE_REQUESTS):
        prompt = rng.integers(0, cfg.vocab_size, int(rng.integers(lo, hi + 1)))
        eng.submit(Request(rid=rid, prompt=[int(t) for t in prompt],
                           max_new=SERVE_MAX_NEW))
    t0 = time.perf_counter()
    results = eng.run()
    wall = time.perf_counter() - t0

    if [r.tag for r in results] != list(range(SERVE_REQUESTS)):
        raise AssertionError(f"tags out of order: {[r.tag for r in results]}")
    for r in results:
        if len(r.generated) != SERVE_MAX_NEW:
            raise AssertionError(f"request {r.rid}: {len(r.generated)} tokens")
        if not all(0 <= t < cfg.vocab_size for t in r.generated):
            raise AssertionError(f"request {r.rid}: token out of vocab")
    if not (finite and all(finite)):
        raise AssertionError(f"non-finite logits in {finite.count(False)} "
                             f"of {len(finite)} decode steps")
    toks = sum(len(r.generated) for r in results)
    steady = wall - step_s[0]
    log("serve", f"{cfg.name}: {n_params / 1e9:.3f}B params, "
                 f"{SERVE_REQUESTS} requests in tag order, {toks} tokens, "
                 f"{eng.steps_run} decode steps, all logits finite")
    log("serve", f"param init {t_params:.2f}s (compile + run); first "
                 f"decode step {step_s[0]:.2f}s (compile + run); "
                 f"{toks / steady:.1f} tok/s after it "
                 f"(smoke run, not a measurement)")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log("serve", f"peak device memory {stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    log("serve", "first requests: " + "; ".join(
        f"rid={r.rid} prompt={len(r.prompt)} out={r.generated[:6]}"
        for r in results[:2]))


def _mesh_ids(prog) -> list:
    return [int(d.id) for d in prog.mesh.devices.flat]


def phase_mesh() -> None:
    """One pipeline-of-farms skeleton, lowered to the mesh backend on the
    chip and to host threads: the outputs must be equal."""
    import numpy as np

    from repro.core import Farm, Pipeline, lower

    xs = [int(x) for x in
          np.random.default_rng(SEED).integers(-1000, 1000, MESH_ITEMS)]
    skel = Pipeline(Farm(mesh_f, 4, ordered=True),
                    Farm(mesh_g, 4, ordered=True))
    on_threads = lower(skel, "threads")(xs)
    prog = lower(skel, "mesh")
    on_mesh = prog(xs)
    if on_mesh != on_threads:
        raise AssertionError("mesh output differs from threads")
    log("mesh", f"Pipeline(Farm, Farm) over {len(xs)} items: mesh == "
                f"threads; mesh {prog.n_stage}x{prog.n_worker} on device "
                f"ids {_mesh_ids(prog)}")


def phase_mesh4(n_devices: int = 4) -> None:
    """The paths that exist only across chips: a pipeline of a farm and a
    feedback loop on a (stage, worker) mesh, and a keyed reduction lowered
    to the all-to-all shuffle program; each equal to the threads backend
    on the same skeleton objects."""
    import numpy as np

    from repro.core import Farm, Feedback, Pipeline, lower, reduce_by_key
    from repro.core.a2a import A2AMeshProgram
    from repro.core.skeleton import MeshProgram

    rng = np.random.default_rng(SEED)
    # non-negative items: each loops 1 to 12 trips and stays inside int32
    xs = [int(x) for x in rng.integers(0, 1000, MESH_ITEMS)]
    skel = Pipeline(Farm(mesh_f, 4, ordered=True),
                    Feedback(fb_step, fb_more, nworkers=4, max_trips=32))
    prog = lower(skel, "mesh")
    ids = _mesh_ids(prog)
    if not isinstance(prog, MeshProgram) or len(set(ids)) != n_devices:
        raise AssertionError(f"pipeline mesh spans {ids}, not {n_devices} "
                             f"distinct devices")
    on_threads = lower(skel, "threads")(xs)
    on_mesh = prog(xs)
    if on_mesh != on_threads:
        raise AssertionError("pipeline+feedback: mesh differs from threads")
    log("mesh4", f"Pipeline(Farm, Feedback) over {len(xs)} items: mesh == "
                 f"threads; mesh {prog.n_stage}x{prog.n_worker} "
                 f"(stage x worker) on device ids {ids}")

    vals = [int(x) for x in rng.integers(0, 1000, MESH_ITEMS)]
    rbk = reduce_by_key(mod_key, "sum", nleft=2, nright=n_devices,
                        nkeys=MESH_NKEYS)
    prog = lower(rbk, "mesh")
    ids = _mesh_ids(prog)
    if not isinstance(prog, A2AMeshProgram) or len(set(ids)) != n_devices:
        raise AssertionError(f"keyed shuffle spans {ids}, not {n_devices} "
                             f"distinct devices")
    want = dict(lower(rbk, "threads")(vals))
    got = dict(prog(vals))
    if got != want:
        raise AssertionError("reduce_by_key: mesh differs from threads")
    log("mesh4", f"reduce_by_key(sum, nkeys={MESH_NKEYS}) over {len(vals)} "
                 f"items: mesh == threads on {len(got)} keys; all-to-all "
                 f"over {prog.n_worker} workers on device ids {ids}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip mesh phase")
    args = ap.parse_args(argv)

    device = phase_device(args.chips)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.runtime.compile_cache import enable_compile_cache
    log("device", f"compile cache {enable_compile_cache()}")

    if args.chips == 4:
        phase_mesh4()
    else:
        from benchmarks.smith_waterman import QUERY_LENS
        from repro.configs import ARCHS
        phase_protein(QUERY_LENS)
        phase_serve(ARCHS[SERVE_ARCH])
        phase_mesh()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
