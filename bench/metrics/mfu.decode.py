"""mfu.decode: model operations of the decode programs traced, over their
device time at the chip's peak bf16 rate, in percent. Nothing to read when
no such program ran in the traced window."""
from bench.flops import program_mfu


def read(obs: dict):
    return program_mfu(obs, "decode")
