"""Share of its HBM roofline that the ``cwmac`` kernel reached in the
traced interval: the HBM bytes its calls need, from their shapes
(``streambench/kernels/cwmac.py``), over the chip's peak HBM bandwidth,
divided by the kernel's summed device time."""
from streambench.roofline import roofline_share


def read(run):
    return roofline_share(run, "cwmac")
