"""The grouped expert FFN's forward kernel against its roofline: the least
time the chip could take for the useful work (routed rows only), over the
device time of the Pallas kernel's events (``_kernels.is_forward``)."""
from chipbench import flops
from chipbench.metrics import _kernels as kernels


def reduce(run):
    return kernels.roofline_share(run, kernels.is_forward,
                                  flops.grouped_ffn_fwd)
