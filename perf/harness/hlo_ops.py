"""Any instruction of a compiled program's text by its ``op_name``.

``perf/harness/hlo.py`` places the Pallas custom calls; this places
every instruction, so that the device time under a ``jax.named_scope``
can be summed: the scope is a path component of the ``op_name`` in the
instruction's metadata (``jit(_decode_step)/moe_experts/...``).  A
fusion carries the ``op_name`` of its root, so a fusion that the
compiler built across two scopes counts under its root's.  Instructions
the TPU compiler rewrites lose the scope (``jax.lax.ragged_dot``
becomes custom calls named ``ragged-dot-none.N`` / ``ragged-dot-
metadata.N`` whose ``op_name`` is just that), so a reader may also name
instructions by a pattern on the instruction's own name.
"""

import re

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(text):
    """{instruction name: op_name ('' when it has none)} over every
    computation of the text."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op.group(1) if op else ""
    return out


def instructions(text, op_pattern=None, name_pattern=None):
    """Names of the instructions whose ``op_name`` matches
    ``op_pattern`` or whose own name matches ``name_pattern``."""
    op_rx = re.compile(op_pattern) if op_pattern else None
    name_rx = re.compile(name_pattern) if name_pattern else None
    return {n for n, op in op_names(text).items()
            if (op_rx and op_rx.search(op)) or (name_rx and name_rx.search(n))}
