"""Arroyo's ``top_n`` query (arroyo-sql-testing, full_query_tests.rs): for
every 10 s window sliding by 2 s, the three auctions with the largest sum of
bid prices.  Plain numpy over the yardstick's own stream; rows are
``[window_end_micros, auction, price]``, ``price`` the auction's sum over the
window, an integer.  The bids are summed per auction per 2 s slide and a
window is the sum of its five slides; of equal sums the lower auction id
ranks first (the engine's rule is by row order, ``assumed.ties``: a tie at
the cut shows as a differing row, and is then reported as such).

``nexmark_gen.batches`` draws a bid's price and throws it away, so the bid
stream with its price is built here: the ids and event times are
``nexmark_gen``'s whole batches, the price is the bid family's fifth
``random(n)`` of each whole batch (after the hot-auction, auction, hot-bidder
and bidder draws), from a generator of its own seeded as the family's is,
and the batches are then cut and faulted as ``nexmark_gen.batches`` cuts and
faults them."""

import numpy as np

from . import nexmark_gen
from .nexmark_q5_counts import _sum_by_auction

SLIDE_MICROS, WIDTH_MICROS, TOP = 2_000_000, 10_000_000, 3
DRAWS_BEFORE_PRICE = 4


def bids(seed, n_events, batch_size, base_time_micros, event_rate,
         before_micros, replay_batch=None, drop_half_of_batch=None):
    """Yield ``(ts, auction, price)`` of the bids of one source batch after
    the other, before ``before_micros``."""
    rng = np.random.default_rng([seed, nexmark_gen.BID_FAMILY])
    whole = nexmark_gen.batches(seed, n_events, batch_size, base_time_micros,
                                event_rate, before_micros=2**62)
    for index, batch in enumerate(whole):
        n = len(batch["ts"])
        for _ in range(DRAWS_BEFORE_PRICE):
            rng.random(n)
        price = nexmark_gen._price(rng, n)
        if index == drop_half_of_batch:
            n //= 2
        keep = ((batch["ts"][:n] < before_micros)
                & (batch["event_type"][:n] == nexmark_gen.BID))
        out = (batch["ts"][:n][keep], batch["bid_auction"][:n][keep],
               price[:n][keep])
        yield out
        if index == replay_batch:
            yield out


def rows(stream, t_end_micros, **faults):
    """Every window that ends at or before ``t_end_micros`` (absolute event
    time) and holds a bid; ``stream`` cuts the events at that time."""
    parts_of = {}  # start of a slide -> (auction, price) parts, per batch
    for ts, auction, price in bids(**stream, **faults):
        slide = ts // SLIDE_MICROS * SLIDE_MICROS
        for start in np.unique(slide).tolist():
            at = slide == start
            parts_of.setdefault(start, []).append((auction[at], price[at]))
    summed = {start: _sum_by_auction(np.concatenate([a for a, _ in parts]),
                                     np.concatenate([p for _, p in parts]))
              for start, parts in parts_of.items()}
    out = []
    for end in range(min(summed) + SLIDE_MICROS, t_end_micros + 1,
                     SLIDE_MICROS):
        inside = [summed[s] for s in range(end - WIDTH_MICROS, end,
                                           SLIDE_MICROS) if s in summed]
        if not inside:
            continue
        auction, price = _sum_by_auction(
            np.concatenate([a for a, _ in inside]),
            np.concatenate([p for _, p in inside]))
        top = np.argsort(-price, kind="stable")[:TOP]
        out.append(np.stack([np.full(len(top), end, dtype=np.int64),
                             auction[top], price[top]], axis=1))
    return np.concatenate(out)
