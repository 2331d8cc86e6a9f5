"""TPC-DS q51 with its ``limit 100`` lifted: every (item, day) on which
the web channel's running total stood above the store's, in the text's
order.  The same DataFrame code (benchmark/queries/tpcds_q51.py
``ordered``: the two aggregates, the four windows, the full join, the
filter, the sort), so the same programs at the same sizes; what differs
is the rows that come back, about 92k at SF10 instead of a hundred.

It is in the cell's traffic beside q51 for the comparison's sake: the
hundred rows with the smallest item keys come from about twenty of the
56,920 partitions, and at SF10 about twenty (item, day) rows a seed hold
two cumulatives that are the SAME number of cents, where ``>`` must be
false: the hundred can be expected to hold none of them, these rows
must leave all of them out."""
import os

from benchmark.harness.cell import load_module

_Q51 = load_module(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "queries", "tpcds_q51")

#: the tables the query scans and the columns it names: q51's
TABLES = _Q51.TABLES


def build(session, data_dir: str):
    return _Q51.ordered(session, data_dir)
