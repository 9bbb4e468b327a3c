"""The program's spans on the profiler's clock, as the span readers match
them.

The program opens a ``tc.count`` span around each count and ``tc.<stage>``
spans inside it (``repro.runtime.spans``). A program without
``repro.runtime.spans`` predates them: its span readers find nothing to
read and return ``None``. A program that has it, but whose trace of a
window with counts holds no ``tc.count`` span, is an error: the spans were
renamed or left the path.
"""
from __future__ import annotations

import importlib.util

COUNT = "tc.count"


def instrumented() -> bool:
    """Whether the program under test emits the spans."""
    try:
        return importlib.util.find_spec("repro.runtime.spans") is not None
    except ModuleNotFoundError:
        return False


def spans(host: list) -> list:
    """The host events that are the program's ``tc.*`` spans."""
    return [e for e in host if e.name.startswith("tc.")]
