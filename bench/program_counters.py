"""Window deltas of the program's own counters, for the metric readers of
the host spans (``metrics/<name>.py``).  A program that keeps no such
counter (one older than its spans) reads as None, never as an error."""


def deltas(run, *keys):
    """The window delta of each of ``keys``, or None where the program's
    counters lack one of them."""
    c0, c1 = run.counters
    if any(k not in c0 or k not in c1 for k in keys):
        return None
    return [run.counter(k) for k in keys]


def ms_per(run, parts, divisor):
    """Sum of the window deltas of ``parts`` (seconds) in milliseconds per
    unit of the ``divisor`` counter's delta; None without the counters or
    with a divisor of 0."""
    d = deltas(run, divisor, *parts)
    if d is None or not d[0]:
        return None
    return sum(d[1:]) / d[0] * 1e3
