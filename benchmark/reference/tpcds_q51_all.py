"""Plain reference for TPC-DS query 51 with its ``limit 100`` lifted
(benchmark/queries/tpcds_q51_all.py): every qualifying row of
benchmark/reference/tpcds_q51.py ``cumulatives``, which is written from
the query text with numpy and pandas and imports nothing of the engine.
Money is ``int64`` cents there until the last step, so a row whose two
cumulatives are the same amount is not among these rows, and one whose
web cumulative is a cent above is.

The rows are ``(int, str, float or None, float or None, float,
float)``."""
import importlib.util
import os


def _q51():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tpcds_q51.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_tpcds_q51", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rows(data_dir: str) -> list:
    ref = _q51()
    return ref.as_rows(ref.qualifying(ref.cumulatives(data_dir)))
