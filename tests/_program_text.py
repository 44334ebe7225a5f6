"""A compiled program's text without the record of who built it.

``compiled.as_text()`` carries, besides the program, the Python call
stack it was traced under: the ``FileNames`` / ``FunctionNames`` /
``FileLocations`` / ``StackFrames`` tables at its head and a
``metadata={...}`` on every instruction. Two builds of one program from
two call sites (a fixture and a test's body) differ there and nowhere
else, so "the same program" is compared on what is left.

A Mosaic kernel's serialized body (``"body":"<base64 MLIR bytecode>"`` in
a ``tpu_custom_call``) carries the same record inside it: the file, line
and column of every op's call site, the checkout's own path among them.
``program_digest`` reads each body back and keeps its ops alone, so two
checkouts, or one whose lines moved, give one digest for one program."""

import base64
import hashlib
import re

_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*\n",
    re.M)
_METADATA = re.compile(r",? metadata=\{[^{}]*\}")


_BODY = re.compile(r'("body":")([A-Za-z0-9+/=]+)')


def program_text(compiled) -> str:
    return _METADATA.sub("", _TABLES.sub("", compiled.as_text()))


def _kernel_ops(match) -> str:
    from jax._src.lib.mlir import ir
    context = ir.Context()
    context.allow_unregistered_dialects = True
    with context:
        module = ir.Module.parse(base64.b64decode(match.group(2)))
        ops = module.operation.get_asm(enable_debug_info=False)
    return match.group(1) + hashlib.sha256(ops.encode()).hexdigest()


def program_digest(compiled) -> str:
    """sha256 of :func:`program_text` with each kernel body replaced by
    the digest of its ops without their locations."""
    text = _BODY.sub(_kernel_ops, program_text(compiled))
    return hashlib.sha256(text.encode()).hexdigest()
