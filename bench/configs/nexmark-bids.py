"""Plain reference for ``nexmark-bids``: the number of bids on each
auction of one tumbling window, by a histogram of the window's auction
ids.  Exact integers; every key in ``[0, nkeys)``, zero where no bid."""
from __future__ import annotations

import numpy as np


def counts(auctions: np.ndarray, nkeys: int) -> np.ndarray:
    return np.bincount(np.asarray(auctions, np.int64), minlength=nkeys)
