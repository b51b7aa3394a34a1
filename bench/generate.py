"""The general generator: every traffic mix and data set is made here from
the parameters in a configuration or traffic file and the run's seed.

A seed changes the order of the work and the values drawn, never its
sizes: lengths come from a fixed draw (``length_seed`` in the file) and
the seed permutes them, so two seeds do the same amount of work.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

AMINO_ACIDS = 20          # codes 0..19 of the BLOSUM order ARNDCQEGHILKMFPSTWYV


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any size) and a named sub-stream."""
    return np.random.default_rng([int(seed) % 2**63, *stream])


# -- protein database search -----------------------------------------------------
@dataclasses.dataclass
class ProteinDB:
    lengths: np.ndarray        # (n,) int64 residues per subject
    offsets: np.ndarray        # (n,) int64 start of each subject in residues
    residues: np.ndarray       # (sum(lengths),) uint8 codes 0..19

    def __len__(self) -> int:
        return len(self.lengths)

    def subject(self, i: int) -> np.ndarray:
        o = self.offsets[i]
        return self.residues[o:o + self.lengths[i]]


def subject_lengths(spec: Dict) -> np.ndarray:
    """Swiss-Prot length statistics (gamma with shape 2 and the release's
    mean, clipped to ``[min_length, max_length]``), as
    ``benchmarks/smith_waterman.py:make_db`` draws them; the same multiset
    for every seed."""
    rng = rng_for(spec["length_seed"])
    lens = rng.gamma(2.0, spec["mean_length"] / 2.0, spec["sequences"])
    return np.clip(lens.astype(np.int64), spec["min_length"],
                   spec["max_length"])


def protein_db(spec: Dict, seed: int) -> ProteinDB:
    """Subjects in an order drawn from the seed within consecutive blocks
    of ``order_block``: every prefix that ends on a block boundary holds
    the same lengths for every seed, so a window does the same work."""
    lengths = subject_lengths(spec)
    rng, block = rng_for(seed, 1), spec["order_block"]
    for start in range(0, len(lengths), block):
        rng.shuffle(lengths[start:start + block])
    offsets = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    residues = rng_for(seed, 2).integers(0, AMINO_ACIDS, int(lengths.sum()),
                                         dtype=np.uint8)
    return ProteinDB(lengths, offsets, residues)


def protein_query(length: int, seed: int) -> np.ndarray:
    return rng_for(seed, 3).integers(0, AMINO_ACIDS, length).astype(np.int32)


# -- NEXmark bids ----------------------------------------------------------------
def nexmark_sizes(gen: Dict, query: Dict) -> Tuple[int, int]:
    """``(window_bids, slide_bids)``: the bids in one sliding window and in
    one period, from the event rate and the window's size and period in
    seconds (events evenly spaced, as Beam's generator spaces them)."""
    total = (gen["person_proportion"] + gen["auction_proportion"] +
             gen["bid_proportion"])
    sizes = []
    for sec in (query["window_size_sec"], query["window_period_sec"]):
        events = gen["first_event_rate"] * sec
        if events % total:
            raise ValueError(f"{events} events is not a whole number of "
                             f"{total}-event epochs")
        sizes.append(events // total * gen["bid_proportion"])
    window, slide = sizes
    if window % slide:
        raise ValueError(f"window of {window} bids is not a whole number "
                         f"of slides of {slide}")
    return window, slide


def nexmark_block(gen: Dict, block: int, size: int, seed: int) -> np.ndarray:
    """Absolute auction ids of bids ``[block*size, (block+1)*size)``, as in
    Apache Beam's NEXmark ``BidGenerator``.

    Events cycle person:auction:bid in ``person:auction:bid`` proportions;
    bid ``b`` sees ``last`` auctions created so far.  With chance
    ``1 - 1/hot_auction_ratio`` it bids on the hot auction (``last`` rounded
    down to a multiple of ``hot_auction_stride``), otherwise on one drawn
    uniformly from the ``in_flight_auctions`` before ``last`` up to
    ``auction_id_lead`` past it."""
    auc, bid = gen["auction_proportion"], gen["bid_proportion"]
    b = np.arange(block * size, (block + 1) * size, dtype=np.int64)
    last = (b // bid) * auc + (auc - 1)         # lastBase0AuctionId
    rng = rng_for(seed, 6, block)
    hot = rng.integers(0, gen["hot_auction_ratio"], size) > 0
    lo = np.maximum(last - gen["in_flight_auctions"], 0)
    cold = lo + rng.integers(0, last - lo + 1 + gen["auction_id_lead"])
    stride = gen["hot_auction_stride"]
    return np.where(hot, (last // stride) * stride, cold)


def nexmark_window(gen: Dict, window: int, bids: int, slide: int,
                   seed: int) -> np.ndarray:
    """The auction ids of sliding window ``window``: bids ``[window*slide,
    window*slide + bids)``, made of whole blocks of ``slide`` bids, so that
    windows that overlap share their bids.  Ids are made relative to the
    lowest auction a bid of this window can name."""
    auction = np.concatenate([nexmark_block(gen, window + k, slide, seed)
                              for k in range(bids // slide)])
    first = window * slide // gen["bid_proportion"]
    last = first * gen["auction_proportion"] + gen["auction_proportion"] - 1
    base = max(last - gen["in_flight_auctions"], 0)
    return (auction - base).astype(np.int32)
