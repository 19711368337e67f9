"""binary_conv_roofline: share of its roofline (%) that the fused binary conv
kernel (kernels/binary_conv.py: stem, point-wise and CNN-A convs) reaches."""
from harness.readers import kernel_roofline

KERNEL = "binary_conv2d_pallas"


def read(ctx):
    return kernel_roofline(ctx, "conv", KERNEL)
