"""Plain numpy NEXMark event stream: the yardstick's own copy of the id
arithmetic of the reference generator (Tucker et al.; Apache Beam's Nexmark
suite; Arroyo's ``nexmark`` connector, mod.rs), cut to the columns q5 and q8
read.  It imports nothing of the program: the stream the program's source
emits for a seed has to equal this one, or every comparison fails.

Proportions person:auction:bid = 1:3:46, ids from 1000, hot sellers 3/4 at
granularity 100, hot auctions 1/2 at granularity 100, 100 auctions in flight,
1000 active people, event times shuffled inside groups of 50 by
``(n * 953) % 50``.  The random draws come from one numpy generator per event
family, seeded ``[seed, family]`` and advanced once per batch, so the batch
size is part of the stream's definition.
"""

import numpy as np

PERSON, AUCTION, BID = 0, 1, 2
PP, AP, BP = 1, 3, 46
TOTAL = PP + AP + BP
FIRST_ID = 1000  # FIRST_PERSON_ID and FIRST_AUCTION_ID
HOT_GRANULARITY = 100  # HOT_SELLER_RATIO, HOT_AUCTION_RATIO, HOT_BIDDER_RATIO
HOT_SELLER_ODDS, HOT_AUCTION_ODDS, HOT_BIDDER_ODDS = 4, 2, 4
INFLIGHT_AUCTIONS, ACTIVE_PEOPLE = 100, 1000
ID_LEAD = 10  # PERSON_ID_LEAD and AUCTION_ID_LEAD
GROUP = 50  # out-of-order group size
AUCTION_FAMILY, BID_FAMILY = 0, 1  # position in the per-family seed


def _price(rng, n):
    return (np.power(10.0, rng.random(n) * 6.0) * 100.0).astype(np.int64)


def _next_person(rng, last_person):
    active = np.minimum(last_person, ACTIVE_PEOPLE)
    n = (rng.random(len(last_person)) * (active + ID_LEAD)).astype(np.int64)
    return last_person - active + n


def _next_auction(rng, last_auction):
    lo = np.maximum(last_auction - INFLIGHT_AUCTIONS, 0)
    span = last_auction + 1 + ID_LEAD - lo
    return lo + (rng.random(len(last_auction)) * span).astype(np.int64)


def batches(seed, n_events, batch_size, base_time_micros, event_rate,
            before_micros, families=("bid",), replay_batch=None,
            drop_half_of_batch=None):
    """Yield dicts of columns, one per source batch: ``ts`` (event time,
    micros), ``event_type`` and, per family asked for, ``bid_auction`` or
    ``auction_seller`` / ``person_id``.  Batches are drawn whole, as the
    source draws them (a family's draws follow one another inside a batch,
    so a shorter batch is another stream), and the events at or after
    ``before_micros`` are then cut.

    ``replay_batch`` delivers that batch twice and ``drop_half_of_batch``
    delivers only its first half: the two ways a run can break exactly-once,
    used by the controls (never by a reference)."""
    delay = max(int(1_000_000.0 / event_rate), 1)
    rng_a = np.random.default_rng([seed, AUCTION_FAMILY])
    rng_b = np.random.default_rng([seed, BID_FAMILY])
    done = index = 0
    while done < n_events:
        n = min(batch_size, n_events - done)
        number = 1 + np.arange(done, done + n, dtype=np.int64)
        done += n
        adjusted = (number // GROUP) * GROUP + (number * 953) % GROUP
        event_id = 1 + adjusted
        rem = event_id % TOTAL
        epoch = event_id // TOTAL
        etype = np.full(n, BID, dtype=np.int8)
        etype[rem < PP] = PERSON
        etype[(rem >= PP) & (rem < PP + AP)] = AUCTION
        last_person = epoch * PP + np.minimum(rem, PP - 1)
        about_person, about_bid = rem < PP, rem >= PP + AP
        last_auction = (np.where(about_person, epoch - 1, epoch) * AP
                        + np.where(about_person | about_bid, AP - 1,
                                   np.clip(rem - PP, 0, AP - 1)))
        out = {"ts": base_time_micros + delay * adjusted,
               "event_type": etype}
        if "person" in families:
            out["person_id"] = np.where(etype == PERSON,
                                        last_person + FIRST_ID, 0)
        if "auction" in families:
            hot = rng_a.random(n) * HOT_SELLER_ODDS >= 1.0
            seller = np.where(
                hot, (last_person // HOT_GRANULARITY) * HOT_GRANULARITY,
                _next_person(rng_a, last_person)) + FIRST_ID
            rng_a.integers(0, 5, n)  # category
            _price(rng_a, n)  # initial bid
            _price(rng_a, n)  # reserve
            rng_a.random(n)  # auction length
            out["auction_seller"] = np.where(etype == AUCTION, seller, 0)
        if "bid" in families:
            hot = rng_b.random(n) * HOT_AUCTION_ODDS >= 1.0
            auction = np.where(
                hot, (last_auction // HOT_GRANULARITY) * HOT_GRANULARITY,
                _next_auction(rng_b, last_auction)) + FIRST_ID
            rng_b.random(n)  # hot bidder
            rng_b.random(n)  # bidder
            _price(rng_b, n)  # price
            out["bid_auction"] = np.where(etype == BID, auction, 0)
        if index == drop_half_of_batch:
            out = {c: v[:n // 2] for c, v in out.items()}
        keep = out["ts"] < before_micros
        out = {c: v[keep] for c, v in out.items()}
        yield out
        if index == replay_batch:
            yield out
        index += 1
