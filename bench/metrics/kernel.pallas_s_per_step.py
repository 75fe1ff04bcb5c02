"""Device seconds per BCD outer step in Pallas (Mosaic) kernels: every
operation whose custom-call target is ``tpu_custom_call``.

The trace names a Pallas call only by that target (the kernel's own name
is not in the op's text), so this metric counts all of them, whichever
kernel it is.  On the paths of the BCD cells today they are the
masked-activation kernels, plain, batched and fused with a conv."""
from bench.lib import trace

PATTERN = r"custom_call_target=\"tpu_custom_call\""


def read(r):
    s = trace.kernel_s(r.trace, PATTERN)
    if not r.steps or s <= 0:
        return None
    return s / len(r.steps)
