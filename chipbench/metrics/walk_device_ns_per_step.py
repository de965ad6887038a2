"""Device busy time in the traced window over the walker-steps sampled in
it (walk engine layer). Moves walk_steps_per_s."""


def read(ctx):
    t = ctx["trace"]
    steps = ctx["counts"].get("walk_steps")
    if t is None or not t.busy_s or not steps:
        return None
    return t.busy_s * 1e9 / steps
