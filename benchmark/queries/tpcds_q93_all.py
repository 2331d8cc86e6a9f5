"""TPC-DS q93 with its ``limit 100`` lifted: every customer's sales net
of the returns made for 'reason 28', in the text's order.  The same
DataFrame code (benchmark/queries/tpcds_q93.py ``ordered``: the two-key
left join, the semi-join on the reason, the ``case``, the group-by, the
sort), so the same programs at the same sizes; what differs is the rows
that come back, about 74k at SF10 instead of the hundred smallest.

It is in the cell's traffic beside q93 for the comparison's sake: at
SF10 all but a few of q93's hundred rows carry a ``sumsales`` of 0.0
(the few are negative: a sale matched with the larger return of a sale
the generator gave the same item and ticket), so they hold the join to
which customers come first and the arithmetic to next to nothing.  These
rows carry every group's sum: a match missed or invented, a price or a
quantity dropped, a product or a sum taken in too few bits moves one of
them past the comparison's tolerance."""
import os

from benchmark.harness.cell import load_module

_Q93 = load_module(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "queries", "tpcds_q93")

#: the tables the query scans and the columns it names: q93's
TABLES = _Q93.TABLES


def build(session, data_dir: str):
    return _Q93.ordered(session, data_dir)
