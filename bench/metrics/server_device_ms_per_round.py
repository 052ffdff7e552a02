"""Device time of every program in the traced window other than the
client group program (the server's replay, aggregation and update
scatters), per round."""

GROUP = "jit_group"


def read(run):
    t = run["trace"]
    if not t:
        return None
    other = [v for k, v in t["programs"].items() if k != GROUP]
    if not other:
        return None
    return sum(other) / len(run["window"]["round_s"]) * 1e3
