"""Share of its HBM roofline that the ``chacha20`` kernel reached in the
traced interval: the HBM bytes its calls need, from their shapes
(``streambench/kernels/chacha20.py``), over the chip's peak HBM bandwidth,
divided by the kernel's summed device time."""
from streambench.roofline import roofline_share


def read(run):
    return roofline_share(run, "chacha20")
