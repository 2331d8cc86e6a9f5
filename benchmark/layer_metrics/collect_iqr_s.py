"""Distance between the first and the third quartile of the window's
untraced collects (host clock; ``statistics.quantiles(n=4)``, the
driver's spread before it is divided by the median): a window whose
collects fall in two modes, or drift, shows here beside the median that
``query_s`` is.  None under two collects.  The runner prints the same
number in the ``window`` fact of every run, from this function."""
import statistics


def quartile_distance(seconds: list) -> float | None:
    if len(seconds) < 2:
        return None
    q1, _, q3 = statistics.quantiles(seconds, n=4)
    return q3 - q1


def read(facts):
    return quartile_distance(facts["counters"]["collect_seconds"])
