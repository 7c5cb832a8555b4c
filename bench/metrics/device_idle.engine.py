"""device_idle.engine: share of the traced window in which no op ran on the chip.

One minus the union of the device's op intervals over the window's length,
in the engine cells; the time the host keeps the chip waiting.
"""


def read(summary, facts, peaks):
    """Percent of the window idle, or None when the trace holds no device op."""
    if summary.window_s <= 0 or not summary.device_ops or not any(summary.device_ops):
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)
