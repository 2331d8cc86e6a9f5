"""The benchmark's harness: one run of one cell (``run``), the files a
cell is made of (``cell``), and the yardstick — comparison, compile
listener, scanned bytes, trace reduction."""
from .runner import run

__all__ = ["run"]
