"""Two pins of position that an appended cell breaks, expected to fail
until a ``benchmark`` PR rewrites them.

The benchmark's contract is append-only: a PR that adds a cell puts its
entries at the END of ``configs``, ``workloads``, ``per_layer`` and of
each metric's ``workloads`` (the driver refuses an entry put anywhere
else as a change to what was there; ``benchtools.ACCEPTED_PER_LAYER``
says the same of ``per_layer``).  PR 35's tests pinned ITS entries to
the end (``[-1]``, ``[-4:]``), which held only until the next cell.
The files are the benchmark's and not a ``model_config`` PR's to edit,
so the two tests are marked here, strictly: the day a ``benchmark`` PR
turns the pins into indexes from the start (PERF.md section 7 gives the
lines) they pass, the strict mark fails, and this file goes.  What they
hold besides position is held, from the start of each list, by
``test_benchmark_keye.py::test_the_accepted_entries_stay_where_they_were``.
"""

import pytest

PINNED_TO_THE_END = {
    "test_benchmark_ax_k1.py::"
    "test_the_cell_is_listed_wherever_the_other_decode_cell_is":
        "asserts configs[-1], workloads[-1] and each list's [-1] are "
        "ax_k1's; a cell appended after it (PR 37) moves none of them "
        "but is now last",
    "test_benchmark_decode.py::test_the_cell_is_listed_where_the_issue_says":
        "asserts per_layer[-4:]; PR 37 appends three metrics after them",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for tail, reason in PINNED_TO_THE_END.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(
                    reason=reason, raises=AssertionError, strict=True))
