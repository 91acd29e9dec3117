"""Expert layer, a chip's share: of the (row, expert) assignments the
window's decode steps made over the router's full width, the share
whose expert is held here: ``moe_assignments_total`` over itself plus
``moe_assignments_elsewhere_total``, phase "decode".  Even routing over
128 experts of which 16 are held reads 12.5."""

from perf.harness import moe


def read(record):
    held = moe.phase_delta(record, "moe_assignments_total", "decode")
    away = moe.phase_delta(record, "moe_assignments_elsewhere_total",
                           "decode")
    if not held or away is None:
        return None
    return 100.0 * held / (held + away)
