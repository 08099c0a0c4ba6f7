"""Tick and window arithmetic, pure functions of the sink's arrival log.

A tick is the arrival of the last sink batch of the window that ends at
event time T: every event before T has then been generated, applied, fired
and delivered.  Between the ticks of T_a and T_b lie exactly
(T_b - T_a) x event_rate events, so a window anchored on two ticks measures
a fixed stretch of the stream whatever the host's clock did before it.
"""

from dataclasses import dataclass


def ticks(window_ends, arrivals):
    """``{window_end: arrival of its last batch}`` from one entry per
    (batch, window end it carries), in arrival order."""
    out = {}
    for end, at in zip(window_ends, arrivals):
        out[end] = max(at, out.get(end, at))
    return out


@dataclass
class Window:
    origin_end: int  # event time (micros) of the origin tick's window end
    close_end: int
    origin_at: float  # host monotonic seconds
    close_at: float
    events: int
    periods: list  # (window_end, arrival, events, seconds) per period

    @property
    def seconds(self):
        return self.close_at - self.origin_at

    @property
    def events_per_s(self):
        return self.events / self.seconds


def close_of(tick_map, origin_end, seconds):
    """Window end of the first tick at or after ``seconds`` past the origin
    tick, or None while no such tick has arrived."""
    origin_at = tick_map.get(origin_end)
    if origin_at is None:
        return None
    for end in sorted(tick_map):
        if end > origin_end and tick_map[end] >= origin_at + seconds:
            return end
    return None


def measure(tick_map, origin_end, seconds, event_rate):
    """The measured window: origin tick to the first tick at or after
    ``seconds`` later.  ``event_rate`` is events per second of event time."""
    close_end = close_of(tick_map, origin_end, seconds)
    if close_end is None:
        raise ValueError(
            f"no tick at or after {seconds} s past the origin tick: "
            f"ticks {sorted(tick_map)} origin {origin_end}")
    inside = [e for e in sorted(tick_map) if origin_end <= e <= close_end]
    late = [e for a, e in zip(inside, inside[1:])
            if tick_map[e] < tick_map[a]]
    if late:
        raise ValueError(f"ticks out of order at window ends {late}")
    per_micro = event_rate / 1e6
    periods = [(e, tick_map[e], round((e - a) * per_micro),
                tick_map[e] - tick_map[a])
               for a, e in zip(inside, inside[1:])]
    return Window(origin_end, close_end, tick_map[origin_end],
                  tick_map[close_end],
                  round((close_end - origin_end) * per_micro), periods)
