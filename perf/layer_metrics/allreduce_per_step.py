"""Parallel: all-reduces in the compiled step's text, as
``chip_smoke.py --chips 4`` counts them."""

import re


def read(record):
    text = record.get("compiled_text", {}).get("step")
    if not text:
        return None
    return len(re.findall(r"\ball-reduce(?:-start)?\(", text))
