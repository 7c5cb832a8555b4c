"""engine_roofline: the bytes a launch must move over HBM peak, over the kernel's time.

A launch must read and write every domain element once,
``2 · itemsize · |domain|`` bytes (``bench.core.counts``); the int32 bodies
have no FLOP bound. The time is the summed device time of the engine's
Pallas kernels in the window. Halo fetches and visits to tiles outside the
domain show as a lower share, never as more work.
"""

from bench.core.counts import engine_needed_bytes


def read(summary, facts, peaks):
    """Percent of the roofline, or None when the window ran no Pallas kernel."""
    kernel_s = summary.kernel_ns() / 1e9
    if kernel_s <= 0 or not facts.get("launches"):
        return None
    needed = facts["launches"] * engine_needed_bytes(facts["m"], facts["n"],
                                                     facts["itemsize"])
    return 100.0 * needed / peaks.hbm_bytes_s / kernel_s
