"""Share (%) of the HBM roofline of the fused heat kernel
(``kernels/stencil3d``): T read, Ci read and T written once per step, over
the peak bandwidth times the kernel's device time."""

from harness import readers


def read(run):
    return readers.kernel_roofline(run, "stencil3d")
