"""NEXMark q8, monitor new users: for every 10 s tumbling window, the persons
who registered in it joined to the sellers who opened an auction in it, each
side with its count.  Plain numpy over the yardstick's own stream; rows are
``[window_end_micros, id, np, na]``."""

import numpy as np

from . import nexmark_gen

TUMBLE = 10_000_000
FAMILIES = ("person", "auction")


def _counts(cells):
    """``{(window, key) cell: count}`` as two sorted arrays."""
    cell, inverse = np.unique(np.concatenate(cells), return_inverse=True)
    return cell, np.bincount(inverse.reshape(-1)).astype(np.int64)


def rows(stream, t_end_micros, **faults):
    """Every window that ends at or before ``t_end_micros`` (absolute event
    time); ``stream`` cuts the events at that time."""
    persons, sellers = [], []
    batches = list(nexmark_gen.batches(families=FAMILIES, **stream,
                                       **faults))
    n_keys = 1 + max(int(max(b["person_id"].max(initial=0),
                             b["auction_seller"].max(initial=0)))
                     for b in batches)
    for b in batches:
        window = b["ts"] // TUMBLE
        is_p = b["event_type"] == nexmark_gen.PERSON
        is_a = b["event_type"] == nexmark_gen.AUCTION
        persons.append(window[is_p] * n_keys + b["person_id"][is_p])
        sellers.append(window[is_a] * n_keys + b["auction_seller"][is_a])
    p_cell, p_n = _counts(persons)
    a_cell, a_n = _counts(sellers)
    both, at_p, at_a = np.intersect1d(p_cell, a_cell, assume_unique=True,
                                      return_indices=True)
    end = (both // n_keys + 1) * TUMBLE
    out = np.stack([end, both % n_keys, p_n[at_p], a_n[at_a]], axis=1)
    return out[end <= t_end_micros]
