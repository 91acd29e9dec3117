"""What a compiled program's text says about its instructions.

The profiler names a device event after its HLO instruction
(``%custom-call.61``, ``%fusion.435``) and, on this setup, gives it no
stats; the text of the compiled program says what the instruction is:
which Pallas kernel a ``tpu_custom_call`` came from (the ``op_name`` of
its metadata) and whether a fusion holds a convolution or a dot.
"""

import re

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")


def custom_calls(text, target="tpu_custom_call"):
    """{instruction name: op_name} of the custom calls to ``target``."""
    out = {}
    for line in text.splitlines():
        if f'custom_call_target="{target}"' not in line:
            continue
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op.group(1) if op else ""
    return out


def kernel_instructions(text, pattern):
    """Names of the Pallas custom calls whose ``op_name`` matches."""
    rx = re.compile(pattern)
    return {n for n, op in custom_calls(text).items() if rx.search(op)}


def categories(text):
    """{instruction name: category} for what the name alone cannot
    tell: a fusion that holds a convolution or a dot (on a TPU a matmul
    is a convolution too) is ``conv/matmul fusion``, a Pallas custom
    call is ``kernel``."""
    bodies, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line.split("(")[0]:
            cur = m.group(1)
            bodies[cur] = []
        elif cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                bodies[cur].append(line)
    heavy = {name for name, lines in bodies.items()
             if any(" convolution(" in ln or " dot(" in ln for ln in lines)}
    out = {n: "kernel" for n in custom_calls(text)}
    for lines in bodies.values():
        for line in lines:
            if " fusion(" not in line:
                continue
            m, c = _INSTR.match(line), _CALLS.search(line)
            if m and c and c.group(1) in heavy:
                out[m.group(1)] = "conv/matmul fusion"
    return out
