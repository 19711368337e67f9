"""binary_matmul_roofline: share of its roofline (%) that the binary matmul
kernel (kernels/binary_matmul.py: dense layers and MobileNet's head)
reaches."""
from harness.readers import kernel_roofline

KERNEL = "binary_matmul_pallas"


def read(ctx):
    return kernel_roofline(ctx, "linear", KERNEL)
