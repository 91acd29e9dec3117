"""Expert layer: device time of a bucketed prefill's instructions
under a ``moe_`` scope (router, dispatch, the grouped GEMMs, combine;
the ``ragged-dot`` custom calls by their own names), all layers, per
run of ``jit__prefill_bucket``, in ms.  A chip that holds part of the
experts runs its grouped GEMMs in a loop over blocks of the assignments
it holds: the loop's instruction spans its body's, which are the ones
counted."""

import re

from perf.harness import hlo_ops, modules, moe
from perf.harness.readers import registry_count

# instructions whose time is that of the instructions they hold
HOLDS_OTHERS = re.compile(r"^(while|conditional|call)\b")


def read(record):
    texts = [t for k, t in record.get("compiled_text", {}).items()
             if k.startswith(moe.PREFILL_PROGRAMS)]
    if not record.get("trace") or not texts:
        return None
    names = set()
    for text in texts:
        names |= hlo_ops.instructions(text, moe.ANY_SCOPE, moe.RAGGED_DOT)
    names = {n for n in names if not HOLDS_OTHERS.match(n)}
    got = names and modules.seconds_in(
        record["trace"], record.get("trace_modules"), moe.PREFILL_MODULE,
        names)
    if not got or not got[1]:
        return None
    # a rehearsal's trace has no line of module runs: the program's count
    runs = got[2] or registry_count(record, "decode_prefill_seconds")
    return got[0] / runs * 1e3 if runs else None
