"""NEXMark q5's inner relation, the count stage of Apache Beam's Query5: for
every 10 s window sliding by 2 s, every auction that has a bid in the window
with the number of its bids there.  Plain numpy over the yardstick's own
stream; rows are ``[window_end_micros, auction, num]``.  It shares nothing
with ``nexmark_q5.py``: the bids are first counted per 2 s slide, and a
window is the sum of the slides it covers."""

import numpy as np

from . import nexmark_gen

SLIDE_MICROS, WIDTH_MICROS = 2_000_000, 10_000_000


def _sum_by_auction(auction, num):
    """Distinct auctions, ascending, with the sum of ``num`` over each."""
    order = np.argsort(auction, kind="stable")
    auction, num = auction[order], num[order]
    first = np.flatnonzero(np.r_[True, auction[1:] != auction[:-1]])
    return auction[first], np.add.reduceat(num, first)


def rows(stream, t_end_micros, **faults):
    """Every window that ends at or before ``t_end_micros`` (absolute event
    time) and holds a bid; ``stream`` cuts the events at that time."""
    bids_of = {}  # start of a slide -> the auctions of its bids, per batch
    for batch in nexmark_gen.batches(families=("bid",), **stream, **faults):
        is_bid = batch["event_type"] == nexmark_gen.BID
        auction = batch["bid_auction"][is_bid]
        slide = batch["ts"][is_bid] // SLIDE_MICROS * SLIDE_MICROS
        for start in np.unique(slide).tolist():
            bids_of.setdefault(start, []).append(auction[slide == start])
    counted = {start: np.unique(np.concatenate(parts), return_counts=True)
               for start, parts in bids_of.items()}
    out = []
    for end in range(min(counted) + SLIDE_MICROS, t_end_micros + 1,
                     SLIDE_MICROS):
        inside = [counted[s] for s in range(end - WIDTH_MICROS, end,
                                            SLIDE_MICROS) if s in counted]
        if not inside:
            continue
        auction, num = _sum_by_auction(
            np.concatenate([a for a, _ in inside]),
            np.concatenate([n for _, n in inside]))
        out.append(np.stack([np.full(len(auction), end, dtype=np.int64),
                             auction, num], axis=1))
    return np.concatenate(out)
