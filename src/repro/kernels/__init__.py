"""Pallas TPU kernels: the feature-map codec and compressed-KV attention.

Every kernel entry point takes ``interpret=None`` and resolves it here:
compiled on TPU, interpreted elsewhere, ``REPRO_CODEC_INTERPRET=0/1`` to
force it (`repro.codec.dispatch.resolve_interpret`).  A direct caller on
a TPU therefore never runs a kernel interpreted without asking to.
"""


def resolve_interpret(interpret: bool | None) -> bool:
    from repro.codec import dispatch  # lazy: repro.codec imports the kernels

    return dispatch.resolve_interpret(interpret)
