"""Share of the traced part in which no operation ran on the device."""
from harness.readers import device_idle as read  # noqa: F401
