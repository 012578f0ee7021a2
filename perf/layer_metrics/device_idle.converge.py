"""Share of the traced window in which no op ran on the device
(1 - busy / window; busy is the union of the device's op intervals)."""

from perf.layer_metrics._spans import device_idle as read  # noqa: F401
