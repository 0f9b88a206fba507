"""flash_roofline.prefill: the Pallas flash forward kernel's share of its
roofline in the prefill programs, in percent: the larger of its operations over
the peak bf16 rate and its bytes over the peak HBM rate, over the device
time of its events. Nothing to read when the kernel did not run there."""
from bench.flops import kernel_roofline


def read(obs: dict):
    return kernel_roofline(obs, "flash.prefill")
