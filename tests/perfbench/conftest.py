"""One case of a test that may not be edited here is marked as expected to fail.

``test_perfbench_rehearsal.py::test_rehearsal_of_each_cell`` runs every cell
of BENCHMARK.json and asserts of each that it sends no companion objects
("an accepted configuration has no companion objects and sends none"), which
was true of every cell when PR 35 wrote it.  ``csi_pvs_5kn.backlog`` (PR 36)
is the cell the companions were built for: it sends a CSINode a node and a
claim and a volume a measured pod, so that assertion fails for it and for
nothing else.  A PR that adds a cell may add files under the benchmark's
paths and may edit none, so the case is marked a strict ``xfail`` here, and
the new cell's rehearsal, with the assertions that fit it, is
``test_perfbench_csi_pvs.py``'s last test.  The case is run (one rehearsal
on the CPU): the day the old assertion is repaired it passes, and the strict
mark turns that into a failure until this file is deleted.  The next
``benchmark`` PR moves the two companion assertions of the old test behind
"a configuration without companions" and deletes this file (PERF.md
section 8).
"""

import pytest

EXPECTED = {
    "test_rehearsal_of_each_cell[csi_pvs_5kn.backlog]":
        "the test asserts companion_objects == 0 of every cell; this cell's companions are its purpose; "
        "its rehearsal is test_perfbench_csi_pvs.py's",
}


def pytest_collection_modifyitems(items):
    for item in items:
        why = EXPECTED.get(item.name)
        if why and item.nodeid.split("::")[0].endswith("test_perfbench_rehearsal.py"):
            item.add_marker(pytest.mark.xfail(reason=why, strict=True))
