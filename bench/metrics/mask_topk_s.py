"""Host seconds of mask calibration's copy of the scores to the host
and its top-k selection: the ``mask.to_host`` and ``mask.topk`` spans of
``core/masks.py`` (``repro/obs.py``).  The copy waits for the gradient
accumulation still running on the device.  None where the program keeps
no such spans."""

SPANS = ("mask.to_host", "mask.topk")


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    t = [s["t1_ns"] - s["t0_ns"] for s in obs.export()["spans"]
         if s["name"] in SPANS]
    return sum(t) * 1e-9 if t else None
