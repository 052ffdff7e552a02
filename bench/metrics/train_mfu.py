"""Model FLOPs the rounds of the traced window require (two forwards per
ZO step; see ``families/<family>.py:forward_flops``) over the traced
window's length times the chip's peak from ``peaks.json``, in percent."""


def read(run):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    w = run["window"]
    flops = run["flops_per_step"] * w["steps_per_round"] * len(w["round_s"])
    return 100.0 * flops / (t["window_s"] * run["peak"]["bf16_flops"])
