"""Over the traced collects: the least time the chip needs to read the
matched string column once — its decoded bytes from the Parquet footers
(benchmark/harness/like_bytes.py; never the padded bytes a kernel
happens to read) over the chip's HBM bandwidth — as a share of the
seconds device 0 spent in the string-match programs (``like_s``).
Bound by HBM bandwidth: a match compares bytes.  None off the chip,
where the trace holds no such program, or where the run's data is not
found."""
import os

from benchmark.harness.like_bytes import MATCH_PROGRAMS, matched_bytes

#: the cell's dataset and the column its query matches
#: (benchmark/queries/tpch_q13.py)
DATASET = "tpch-sf10"
MATCHED = {"orders": ["o_comment"]}

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(facts, root: str = _ROOT, dataset: str = DATASET):
    peaks, trace = facts["peaks"], facts["trace"]
    seconds = sum(s for name, s in trace["device_ops"] if name in MATCH_PROGRAMS)
    if peaks is None or not seconds or not trace["collects"]:
        return None
    nbytes = matched_bytes(root, dataset, MATCHED)
    if not nbytes:
        return None
    least_s = len(trace["collects"]) * nbytes / (
        peaks["hbm_bytes_per_s"] * facts["counters"]["chips"])
    return 100.0 * least_s / seconds
