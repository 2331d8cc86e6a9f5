"""Plain reference for TPC-DS query 93 with its ``limit 100`` lifted
(benchmark/queries/tpcds_q93_all.py): every group of
benchmark/reference/tpcds_q93.py ``aggregate``, which is written from
the query text with pandas and imports nothing of the engine.  No row
is cut off, so there is no near tie to refuse: the rows are compared
without their order, and equal sums are equal to the cent.

The rows are ``(int or None, float or None)``."""
import importlib.util
import os


def _q93():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tpcds_q93.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_tpcds_q93", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rows(data_dir: str) -> list:
    ref = _q93()
    return ref.as_rows(ref.aggregate(data_dir))
