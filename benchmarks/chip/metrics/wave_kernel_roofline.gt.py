"""The wave kernel's share of its memory roofline in the traced part:
bytes of the problem (real lanes, μops and operand reads, counted by the
traffic driver from the configuration's tables) over the HBM bandwidth,
divided by the device time of the kernel (``jit_run``) in the trace."""
from harness.readers import wave_kernel_roofline as read  # noqa: F401
