"""The serving launcher driven in-process through ``main(argv)`` at the
reduced CPU size: the same code path ``chip_smoke.py`` runs on a TPU at
published widths."""
from repro.launch import serve
from repro.serving.runtime import RuntimeReport


def test_launcher_main_returns_report():
    report = serve.main(["--reduced", "--queries", "2",
                         "--cloud-replicas", "2"])
    assert isinstance(report, RuntimeReport)
    assert report.n == 2
    for res in report.results:
        assert set(res.results) == set(res.dag.sids)
        assert all(r.tok_out > 0 for r in res.results.values())
    stats = report.stats
    for counter in ("retries", "degraded", "exec_faults", "cloud_deaths",
                    "cloud_failovers"):
        assert stats[counter] == 0, counter
    assert stats["cloud_replica_errors"] == []
    assert stats["edge_engine"]["tokens_out"] > 0
    assert stats["cloud_engine"]["replicas"] == 2
