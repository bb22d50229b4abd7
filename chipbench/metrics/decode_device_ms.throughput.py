"""``decode_device_ms``, read by the same reader, in the cells judged on
``tokens_per_s``."""
from pathlib import Path

from chipbench.registry import load_reader

read = load_reader("decode_device_ms", Path(__file__).resolve().parent)
