"""Device busy time in the traced window over the SGNS batch steps trained
in it (trainer layer; the walk rounds of the SGNS cells share the busy
time). Moves sgns_pairs_per_s."""


def read(ctx):
    t = ctx["trace"]
    steps = ctx["counts"].get("sgns_steps")
    if t is None or not t.busy_s or not steps:
        return None
    return t.busy_s * 1e6 / steps
