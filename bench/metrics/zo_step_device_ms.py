"""Device time of the client group program (the jit of
``FederatedZO._batch_run_for``'s ``group``) in the traced window, per ZO
step it ran."""

PROGRAM = "jit_group"


def read(run):
    t = run["trace"]
    if not t or PROGRAM not in t["programs"]:
        return None
    w = run["window"]
    return t["programs"][PROGRAM] / (len(w["round_s"])
                                     * w["steps_per_round"]) * 1e3
