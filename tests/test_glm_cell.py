"""The ``glm-5-generate-longctx`` cell rehearsed end to end on the CPU
(``perf/tests/test_glm_cell.py``'s case, imported and not copied): a
subprocess that serves the toy model, verifies it against the reference
and reads every metric the cell lists.  In a file of its own so that
the suite's workers share the three rehearsals (the other two run from
``tests/test_perf_harness.py``, with the cell's other cases)."""

import pytest

pytest.register_assert_rewrite("perf.tests.test_glm_cell")

from perf.tests.test_glm_cell import (  # noqa: E402,F401
    test_the_cell_rehearses_traced_and_reads_what_it_lists)
