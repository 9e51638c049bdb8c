"""Which device ops of a TPU trace are which of the program's kernels.

A TPU trace names an op event by its HLO instruction, and a Pallas kernel
is a `custom-call` to `tpu_custom_call` that carries no kernel name, so a
kernel is told by its operands and results:

  * `attend_paged` takes its two scalar-prefetch operands first: the (B,)
    positions and the (B, nblocks) block table, both s32;
  * the fused compress kernel returns the int8 codes and the f32 scales as
    a tuple, and is called through the codec's `_compress_blocks` jit.

Outer ops that only contain others (`while`, `conditional`, `call`) are
not kernels; the union of intervals counts a nested op once.
"""
from __future__ import annotations

import re

_TPU_KERNEL = 'custom_call_target="tpu_custom_call"'
_ATTEND = re.compile(r"custom-call\(s32\[\d+\]\S* %\S+, s32\[\d+,\d+\]")
_TUPLE_S8 = re.compile(r"^%?\S+ = \(s8\[")
CONTAINERS = ("while", "conditional", "call")


def is_attend_paged(op: str, text: str) -> bool:
    return _TPU_KERNEL in text and bool(_ATTEND.search(text))


def is_compress(op: str, text: str) -> bool:
    return op.startswith("_compress_blocks") or (
        _TPU_KERNEL in text and bool(_TUPLE_S8.search(text)))


def label(op: str, text: str) -> str:
    """A name for the breakdown: the kernel's, else `<opcode> <op>`."""
    from chipbench.trace import opcode

    if is_attend_paged(op, text):
        return "attend_paged (kernels/fused_attend)"
    if is_compress(op, text) and _TPU_KERNEL in text:
        return "compress (kernels/fused_compress)"
    return f"{opcode(text) or '?'} {op}"
