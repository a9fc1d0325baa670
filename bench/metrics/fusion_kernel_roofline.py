"""The Floe fusion kernel (``kernels/logit_fusion``, Eq. 15) against its
roofline: the least time the chip needs for each execution's bytes (per
memory space, from the shapes in its op text) and operations
(``flops.op_least_seconds``), over its device time, in the traced
window."""
import layers


def read(run):
    share = layers.fusion_kernel_share(run)
    return None if share is None else share * 100.0
