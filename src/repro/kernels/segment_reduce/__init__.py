from repro.kernels.segment_reduce.kernel import MAX_C
from repro.kernels.segment_reduce.ops import segment_reduce
from repro.kernels.segment_reduce.ref import PAD_KEY, segment_reduce_ref

__all__ = ["segment_reduce", "segment_reduce_ref", "PAD_KEY", "MAX_C"]
