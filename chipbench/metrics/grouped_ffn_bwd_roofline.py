"""The grouped expert FFN's backward against its roofline: the least time
for the useful work (the input and weight gradients of the routed rows),
over the device time of its ragged dots (``_kernels.is_backward``)."""
from chipbench import flops
from chipbench.metrics import _kernels as kernels


def reduce(run):
    return kernels.roofline_share(run, kernels.is_backward,
                                  flops.grouped_ffn_bwd)
