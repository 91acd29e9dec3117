"""Expert layer, a chip's share: of the sorted assignments the window's
grouped GEMMs ran over (blocks x a block's rows:
``moe_grouped_rows_total{rows="computed"}``), the share that were some
held expert's group (a live row's assignment to an expert held here:
``rows="assigned"``).  A block is sized at twice what even routing
sends here, so even routing and no padding reads 50; a bucket's padding
rows are no group's and read as room."""

from perf.harness import tick_account as ta

ROWS = "moe_grouped_rows_total"


def read(record):
    reg = record.get("registry")
    if not reg or ROWS not in reg["after"]:
        return None

    def delta(rows):
        return (ta.total(reg["after"], ROWS, rows=rows)
                - ta.total(reg["before"], ROWS, rows=rows))
    computed = delta("computed")
    return 100.0 * delta("assigned") / computed if computed else None
