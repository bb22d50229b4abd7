"""``compiles_in_window``, read by the same reader, in the cells judged on
``tokens_per_s``."""
from pathlib import Path

from chipbench.registry import load_reader

read = load_reader("compiles_in_window", Path(__file__).resolve().parent)
