"""The CPU test widths of the benchmark configurations that
`bench/tests/rehearse.py`'s `SMALL` does not list: `make_root` cuts every
configuration of `BENCHMARK.json`, so each needs an entry before a
rehearsal runs. They are added once collection has ended, so that tests
parametrised over `SMALL`'s dense GQA configurations keep their cases."""
from bench.tests.small_mla_moe import KIMI_SMALL


def pytest_collection_finish(session):
    from bench.tests.rehearse import SMALL
    SMALL.setdefault("kimi-k2-instruct-chip", KIMI_SMALL)
