"""binary_dwconv_roofline: share of its roofline (%) that the fused binary
depth-wise kernel (kernels/binary_dwconv.py) reaches."""
from harness.readers import kernel_roofline

KERNEL = "binary_dwconv2d_pallas"


def read(ctx):
    return kernel_roofline(ctx, "dwconv", KERNEL)
