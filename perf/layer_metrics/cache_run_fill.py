"""The page run's pool: run pages in use over the pool's usable pages,
the mean over the window's decode steps
(``decode_run_pages_in_use_steps_total`` over ``decode_steps_total`` x
(``num_pages`` - 1): page 0 is the null page), in %.  Whether the
traffic fills the memory the cut left; a reservation counts a request's
whole budget, so it reads above the rows resident."""

from perf.harness import mimo
from perf.harness.readers import registry_count


def read(record):
    page_steps = mimo.counted(record, mimo.RUN_PAGE_STEPS)
    steps = registry_count(record, mimo.STEPS)
    pages = record["config"]["generate"].get("num_pages")
    if not page_steps or not steps or not pages:
        return None
    return 100.0 * page_steps / (steps * (pages - 1))
