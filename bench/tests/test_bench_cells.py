"""The cell machinery: ``BENCHMARK.json`` resolves to files found by name,
a new cell is new files and entries only, and the generator gives every
seed the same sizes."""
import copy
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from bench import generate, harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = harness.load_cell(cell)
    assert (ROOT / "bench" / "drivers" / f"{c.config['driver']}.py").exists()
    assert harness.reference(c.config) is not None
    assert "setup_s" in [m["name"] for m in c.end_to_end]
    assert len(c.end_to_end) >= 2 and c.per_layer
    moved = {m["name"] for m in c.end_to_end}
    for m in c.per_layer:
        assert m["moves"] in moved
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_only_the_shuffle_cell_takes_four_chips():
    assert {w["name"]: w["chips"] for w in SPEC["workloads"]
            if w["chips"] != 1} == {"nexmark-q5count-4chip": 4}


def test_a_new_cell_is_data_only(tmp_path):
    """Copy the benchmark, add a traffic file and one entry: the new cell
    runs without an edit to any file that was there."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = copy.deepcopy(SPEC)
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    mix = json.loads((ROOT / "bench/traffic/q144.json").read_text())
    mix.update(query_length=12, gap_open=5, why="a new mix")
    (tmp_path / "bench/traffic/q12-gap5.json").write_text(json.dumps(mix))
    spec["workloads"] = SPEC["workloads"] + [
        {"name": "sw-q12-gap5", "config": "swissprot-57.5-search",
         "traffic": "q12-gap5", "chips": 1, "why": "a new cell"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "sw-q144" in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["sw-q12-gap5"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("sw-q12-gap5", tmp_path / "BENCHMARK.json",
                             tmp_path / "bench")
    assert cell.traffic["query_length"] == 12
    assert [m["name"] for m in cell.per_layer] == \
        ["sw.pair_ms", "sw.kernel_gcups", "device_idle.sw"]
    assert all(p.read_bytes() == b for p, b in before.items())


def test_seeds_change_order_not_sizes():
    spec = dict(sequences=5000, mean_length=352, min_length=2,
                max_length=2000, length_seed=575, order_block=1024)
    a, b = generate.protein_db(spec, 1), generate.protein_db(spec, 2**40 + 3)
    assert list(a.lengths) != list(b.lengths)
    for end in (1024, 3072, 5000):        # every block boundary, and the end
        assert sorted(a.lengths[:end]) == sorted(b.lengths[:end])
    assert a.residues.max() < generate.AMINO_ACIDS
    assert np.array_equal(generate.protein_db(spec, 1).residues, a.residues)


def test_nexmark_window_keys_fit_the_key_space():
    cfg = json.loads((ROOT / "bench/configs/nexmark-bids.json").read_text())
    gen, q = cfg["generator"], cfg["query"]
    bids, slide = generate.nexmark_sizes(gen, q)
    # Beam's defaults: 10 s windows every 5 s at 10000 events/s, 46 of
    # every 50 events a bid
    assert (bids, slide) == (92000, 46000)
    for w in (0, 1, 3):
        ids = generate.nexmark_window(gen, w, bids, slide, 11)
        assert len(ids) == bids and 0 <= ids.min() and ids.max() < q["nkeys"]
    counts = np.bincount(ids)
    # half of the bids go to a hot auction, one per 100 auctions
    hot = np.sort(counts)[::-1][:len(counts) // 100 + 2].sum()
    assert 0.45 < hot / len(ids) < 0.6


def test_sliding_windows_share_their_bids():
    """Window w's newer half is window w+1's older half: every bid is
    counted in two windows, as Q5's sliding windows count it."""
    cfg = json.loads((ROOT / "bench/configs/nexmark-bids.json").read_text())
    gen = cfg["generator"]
    a = generate.nexmark_block(gen, 5, 46000, 3)
    w4 = generate.nexmark_window(gen, 4, 92000, 46000, 3)
    w5 = generate.nexmark_window(gen, 5, 92000, 46000, 3)
    # the same auctions, each made relative to its own window's base
    assert np.array_equal(w4[46000:] - w4[46000:].min(),
                          w5[:46000] - w5[:46000].min())
    assert np.array_equal(w5[:46000] - w5[:46000].min(), a - a.min())
    assert not np.array_equal(generate.nexmark_block(gen, 5, 46000, 4), a)


def _gotoh(q, d, go, ge):
    """Smith-Waterman with affine gaps, cell by cell."""
    blosum = harness.reference({"name": "swissprot-57.5-search"}).BLOSUM50
    neg = -10 ** 9
    H = [[0] * (len(d) + 1) for _ in range(len(q) + 1)]
    E = [[neg] * (len(d) + 1) for _ in range(len(q) + 1)]
    F = [[neg] * (len(d) + 1) for _ in range(len(q) + 1)]
    best = 0
    for i in range(1, len(q) + 1):
        for j in range(1, len(d) + 1):
            E[i][j] = max(H[i][j - 1] - go, E[i][j - 1] - ge)
            F[i][j] = max(H[i - 1][j] - go, F[i - 1][j] - ge)
            H[i][j] = max(0, H[i - 1][j - 1] + int(blosum[q[i - 1], d[j - 1]]),
                          E[i][j], F[i][j])
            best = max(best, H[i][j])
    return best


@pytest.mark.parametrize("gaps", [(10, 2), (2, 2), (11, 1)])
def test_search_reference_is_the_textbook_recurrence(gaps):
    ref = harness.reference({"name": "swissprot-57.5-search"})
    rng = np.random.default_rng(sum(gaps))
    q = rng.integers(0, 20, 30)
    subjects = [rng.integers(0, 20, n) for n in (1, 7, 25, 40)]
    # one subject that holds the query with an insertion: long gaps pay
    subjects.append(np.concatenate([q[:12], rng.integers(0, 20, 9), q[12:]]))
    got = ref.scores(q, subjects, *gaps)
    assert list(got) == [_gotoh(q, s, *gaps) for s in subjects]


def test_run_without_a_tpu_exits_nonzero_and_says_so():
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload", "sw-q144",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""
