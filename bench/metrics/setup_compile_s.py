"""Seconds the program spent compiling before the first timed round:
the union of the intervals of its compile records (``repro/obs.py``:
trace, lowering and backend compile of every program, a persistent-cache
load included) that end before the first window round's ``fl.round``
span opens.  Nested traces count once.  None where the program keeps no
such records."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    rec = obs.export()
    rounds = sorted(s["t0_ns"] for s in rec["spans"]
                    if s["name"] == "fl.round")
    n = len(run["window"]["round_s"])
    if not rec["compiles"] or len(rounds) <= n:
        return None
    start = rounds[-n]
    ivs = sorted((c["t1_ns"] - c["seconds"] * 1e9, c["t1_ns"])
                 for c in rec["compiles"] if c["t1_ns"] <= start)
    total, end = 0.0, float("-inf")
    for s, e in ivs:
        if e > end:
            total += e - max(s, end)
            end = e
    return total * 1e-9
