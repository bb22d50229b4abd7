"""The ``latent_read_share`` reader on hand-made engine counters."""
from types import SimpleNamespace as NS

import pytest

from chipbench import registry


def test_latent_read_share_is_lines_read_over_lines_stored():
    read = registry.load_reader("latent_read_share")
    c = {"steps": 3, "latent_lines_read": 3 * 1536,
         "latent_lines_stored": 3 * 4 * 4096}
    assert read(NS(result={"counters": {"engine": c}})) == \
        pytest.approx(100 * 1536 / (4 * 4096))
    # a program without the counter, or that never decoded: nothing
    for c in ({"steps": 3}, {"latent_lines_read": 0,
                             "latent_lines_stored": 0}):
        assert read(NS(result={"counters": {"engine": c}})) is None
    assert read(NS(result={})) is None
