"""engine_speedup_vs_bb: the bounding box's kernel time over the production kind's.

A traced run ends with a few launches of the same body at the same ``n``
under ``kind="bb"`` (the mix's ``compare_kind``), traced apart from the
window. The ratio of the Pallas kernel's device time per launch there to its
time per launch in the window is the paper's speedup of the simplex map
over the bounding box. It needs the ``bb`` kind to stay registered.
"""


def read(summary, facts, peaks):
    """The ratio, or None when either side ran no Pallas kernel."""
    window_ns = summary.kernel_ns()
    compare_ns = summary.compare.kernel_ns() if summary.compare else 0.0
    if window_ns <= 0 or compare_ns <= 0 or not facts.get("compare_launches"):
        return None
    return (compare_ns / facts["compare_launches"]) / (window_ns / facts["launches"])
