"""NEXMark q5, hot items: for every 10 s window sliding by 2 s, the auctions
whose bid count equals the window's maximum.  Plain numpy over the yardstick's
own stream; rows are ``[window_end_micros, auction, num]``."""

import numpy as np

from . import nexmark_gen

SLIDE, BINS = 2_000_000, 5  # HOP(2 s, 10 s): a window is five 2 s bins
FAMILIES = ("bid",)


def rows(stream, t_end_micros, **faults):
    """Every window that ends at or before ``t_end_micros`` (absolute event
    time); ``stream`` cuts the events at that time."""
    n_bins = t_end_micros // SLIDE + 1  # bin index space (absolute bins)
    keys, counts = [], []
    for b in nexmark_gen.batches(families=FAMILIES, **stream, **faults):
        bid = b["event_type"] == nexmark_gen.BID
        cell, cnt = np.unique(
            b["bid_auction"][bid] * n_bins + b["ts"][bid] // SLIDE,
            return_counts=True)
        keys.append(cell)
        counts.append(cnt)
    cell, inverse = np.unique(np.concatenate(keys), return_inverse=True)
    cnt = np.bincount(inverse, weights=np.concatenate(counts)).astype(np.int64)
    auction, bin_ = cell // n_bins, cell % n_bins
    out = []
    for last in range(int(bin_.min()), int(t_end_micros // SLIDE)):
        sel = (bin_ > last - BINS) & (bin_ <= last)
        if not sel.any():
            continue
        ids, inv = np.unique(auction[sel], return_inverse=True)
        total = np.bincount(inv, weights=cnt[sel]).astype(np.int64)
        hot = total == total.max()
        end = np.full(int(hot.sum()), (last + 1) * SLIDE, dtype=np.int64)
        out.append(np.stack([end, ids[hot], total[hot]], axis=1))
    return np.concatenate(out)
