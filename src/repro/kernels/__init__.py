"""Pallas TPU kernels for the QMM hot-spots (validated in interpret mode).

- ``binary_qmm``    fused unpack -> MXU int8 dot (the default TPU datapath)
- ``popcount_qmm``  AND+popcount on packed words (faithful DPU analogue)
- ``bitserial_qmm`` multi-bit act x act over bit-planes (Fig. 4 schedule)
- ``fused_qmm``     whole bit-serial schedule + affine epilogue in one kernel
- ``decode_attn``   single-token attention over the int8 GQA cache, live rows only
- ``ops``           jit'd wrappers: padding, dispatch, flow epilogue
- ``ref``           pure-jnp oracles (the correctness contracts)
"""

from repro.kernels import (
    binary_qmm,
    bitserial_qmm,
    decode_attn,
    fused_qmm,
    ops,
    popcount_qmm,
    ref,
)

__all__ = ["binary_qmm", "bitserial_qmm", "decode_attn", "fused_qmm", "ops", "popcount_qmm", "ref"]
