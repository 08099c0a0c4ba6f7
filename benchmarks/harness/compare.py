"""The comparison that decides ``correct``: the rows the sink received for
every window that fired up to the close tick, against the plain reference's
rows for the same stretch of the stream.  Counts and maxima are exact, so
every number compared has the limit 0."""

import numpy as np

LIMITS = {"windows_missing": 0, "windows_extra": 0, "rows_differ": 0}


def sink_rows(batches, columns, t_end_micros):
    """``[window_end, columns...]`` of the sink's rows whose window ended at
    or before ``t_end_micros`` (a fired row's timestamp is window_end - 1)."""
    parts = [np.stack([np.asarray(b.timestamp, dtype=np.int64) + 1]
                      + [np.asarray(b.columns[c]).astype(np.int64)
                         for c in columns], axis=1)
             for b in batches if len(b)]
    rows = (np.concatenate(parts) if parts
            else np.zeros((0, 1 + len(columns)), dtype=np.int64))
    return rows[rows[:, 0] <= t_end_micros]


def compare(got, want):
    """The numbers compared, each a count that has to be 0: windows of the
    reference that the sink lacks, windows of the sink that the reference
    lacks, and rows (as a multiset) on one side only; beside them, not
    compared, the windows that hold such a row."""
    got_w, want_w = np.unique(got[:, 0]), np.unique(want[:, 0])
    both = np.concatenate([got, want])
    uniq, inverse = np.unique(both, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    in_got = np.bincount(inverse[:len(got)], minlength=len(uniq))
    in_want = np.bincount(inverse[len(got):], minlength=len(uniq))
    wrong = in_got != in_want
    return {"windows_missing": int(len(np.setdiff1d(want_w, got_w))),
            "windows_extra": int(len(np.setdiff1d(got_w, want_w))),
            "rows_differ": int(np.abs(in_got - in_want).sum()),
            "windows_wrong": int(len(np.unique(uniq[wrong, 0])))}


def verdict(numbers):
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def lines(numbers):
    """One line per number compared, beside its limit."""
    return [f"compared {k} {numbers[k]} limit {LIMITS[k]}" for k in LIMITS]
