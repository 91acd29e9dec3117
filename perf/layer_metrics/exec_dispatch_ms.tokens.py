"""Executor: the program's ``executor.step`` span, the dispatch of the
jitted step (returns before the device is done), mean, LM training
cell."""

from perf.harness.program_spans import exec_dispatch_ms as read  # noqa: F401
