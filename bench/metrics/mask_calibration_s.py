"""Host clock around ``sensitivity_mask``, ending when the mask's indices
are on the device."""


def read(run):
    return run["spans"].get("mask_calibration")
