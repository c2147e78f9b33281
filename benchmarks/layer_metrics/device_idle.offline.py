"""1 - union of device-op intervals over the traced window."""
from _shared import device_idle_percent as read  # noqa: F401
