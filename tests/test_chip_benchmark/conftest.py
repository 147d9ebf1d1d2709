"""One accepted test of this directory states an invariant that no PR after PR 27 can keep:
``test_scopes.py::test_benchmark_json_lists_the_new_metrics_last_and_every_cell_reports_them`` asserts that
PR 27's seven metrics are the LAST entries of ``per_layer``, that none of them has a ``workloads`` list, and
that every cell reports all seven. The contract of the benchmark says new entries go at the end of their
lists, and that an accepted metric whose reader finds nothing in a new cell gets the list of the accepted
cells that report it. PR 29 adds a cell with no replay prefetcher (nothing for ``prefetch_*`` and
``h2d_mib_per_step`` to read) and appends its own metrics, as ISSUE 29 asks. A PR that adds to the benchmark
may not edit a file the benchmark already has, so the test is marked as an expected failure here, and
``test_seq_cell.py::test_accepted_metrics_keep_their_entries_and_their_cell`` asserts what is left of it: the
seven entries unchanged but for that list, in their order, and ``dv3_xl.chip_player`` reporting all seventeen.
A ``benchmark`` PR can rewrite the test and delete this file.
"""

import pytest

SUPERSEDED = "test_scopes.py::test_benchmark_json_lists_the_new_metrics_last_and_every_cell_reports_them"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(SUPERSEDED):
            item.add_marker(pytest.mark.xfail(reason="PR 27's metrics are no longer the last entries, and a cell without a prefetcher does not report four of them (PR 29)", strict=False))
