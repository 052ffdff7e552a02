"""Client training tokens (batch x seq_len x ZO steps, over all clients)
of every whole round in the window, over the wall time of those rounds."""


def read(run):
    w = run["window"]
    return w["tokens_per_round"] * len(w["round_s"]) / sum(w["round_s"])
