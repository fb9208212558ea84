"""Device time of the Pallas pack kernel as a share (%) of the busy time.
The program's one Pallas kernel is ``_pack_kernel``; every Pallas
instruction in the trace is counted."""

from bench.harness import readers

KERNEL = r""


def read(ctx):
    return readers.kernel_share(ctx, KERNEL)
