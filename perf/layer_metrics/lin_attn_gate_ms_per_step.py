"""Linear-attention layers: device time of the decode step's
instructions under ``lin_attn_gate`` (a KDA layer's per-channel decay
and beta: the ``W_f`` and ``W_b`` projections and the bounded gate),
all linear layers, per decode step, in ms."""

from perf.harness import ling_hybrid


def read(record):
    return ling_hybrid.step_scope_ms(record, ling_hybrid.GATE_SCOPE)
