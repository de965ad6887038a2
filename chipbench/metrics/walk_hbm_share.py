"""Essential walk bytes (``chipbench/core/work.py``) over device busy time
times the chip's peak HBM bandwidth, in percent (walk engine / kernels).
Moves walk_steps_per_s."""


def read(ctx):
    t = ctx["trace"]
    nbytes = ctx["counts"].get("walk_bytes")
    if t is None or not t.busy_s or not nbytes or ctx["peaks"] is None:
        return None
    return 100.0 * nbytes / (t.busy_s * ctx["peaks"]["hbm_bytes_per_s"])
