"""Executor: the host work of one ``exe.run`` before its dispatch (the
program's ``executor.feed`` + ``executor.lookup`` +
``executor.gather_state`` spans), mean per run, ResNet cells."""

from perf.harness.program_spans import exec_prepare_ms as read  # noqa: F401
