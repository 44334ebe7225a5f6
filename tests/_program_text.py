"""A compiled program's text without the record of who built it.

``compiled.as_text()`` carries, besides the program, the Python call
stack it was traced under: the ``FileNames`` / ``FunctionNames`` /
``FileLocations`` / ``StackFrames`` tables at its head and a
``metadata={...}`` on every instruction. Two builds of one program from
two call sites (a fixture and a test's body) differ there and nowhere
else, so "the same program" is compared on what is left."""

import re

_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*\n",
    re.M)
_METADATA = re.compile(r",? metadata=\{[^{}]*\}")


def program_text(compiled) -> str:
    return _METADATA.sub("", _TABLES.sub("", compiled.as_text()))
