"""Traffic kind ``sgns_pipeline``: FN-Multi rounds, each walked by the
program's ``WalkEngine.run`` and then trained by its
``StreamingSGNSTrainer.consume`` (one epoch program a round, dense Adam).

Set-up builds the walk engine and the trainer and drives the trainer
through its first round (round 0), which compiles both programs. The window
then runs whole rounds until ``seconds`` have passed, and closes when the
last round's training has finished on the device. Once it has closed, the
plain reference replays every round the trainer took, round 0 and the
window's, over the same walks from the same seed, and each step's loss and
the state after the last round are compared. Starts are drawn as in
``walk_rounds``.
"""
from __future__ import annotations

import numpy as np

from chipbench.core import sgns_reference, work
from chipbench.core.walker import Prepared, Walker, timed_rounds

GROUPS = ("walk", "train")


def _trainer_seed(seed: int) -> int:
    ss = np.random.SeedSequence([seed % (1 << 64), 1])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


def prepare(cell, seed: int, seconds: float, span, *, control: bool = False):
    """As ``walk_rounds.prepare``. ``control`` puts the reference, computed
    in bfloat16, in the trainer's place for the numbers compared."""
    import jax
    import jax.numpy as jnp
    from repro.train import StreamingSGNSTrainer

    cfg = cell.config
    walker = Walker(cfg, cell.traffic["walkers_per_round"], seed, span)
    sgns = dict(vocab=walker.g.n, dim=cfg["dim"], window=cfg["window"],
                negatives=cfg["negatives"], batch=cfg["batch_size"],
                lr=cfg["lr"], power=cfg["power"], seed=_trainer_seed(seed))
    with span("setup.trainer"):
        trainer = StreamingSGNSTrainer(
            sgns["vocab"], dim=sgns["dim"], window=sgns["window"],
            negatives=sgns["negatives"], batch_size=sgns["batch"],
            lr=sgns["lr"], epochs=1, seed=sgns["seed"],
            sgns_backend=cfg["sgns_backend"], power=sgns["power"])
        # the tables as they start, for the norm of their change
        start = {k: jnp.array(v) for k, v in trainer.params.items()}

    with span("setup.warmup"):
        _, walks0 = walker.run(0)
        trainer.consume(walks0)
        jax.block_until_ready((trainer.params, trainer.opt_state))

    def one_round(r):
        with span("walk.run"):
            s, w = walker.run(r)
        with span("train.consume"):
            trainer.consume(w)
        return s, w

    def finish():
        with span("host.finalize"):
            jax.block_until_ready((trainer.params, trainer.opt_state))

    def counts(rounds) -> dict:
        good = walker.complete(rounds)
        steps = sum(-(-work.sgns_pairs_all(w, sgns["window"])
                      // sgns["batch"]) for _, w in good)
        return {**walker.counts(rounds),
                "sgns_pairs": sum(work.sgns_pairs(w, sgns["window"])
                                  for _, w in good),
                "sgns_steps": steps,
                "sgns_bytes": steps * work.sgns_step_bytes(
                    sgns["batch"], sgns["negatives"], sgns["dim"])}

    def rates(counts, window_s) -> dict:
        return {"sgns_pairs_per_s": counts["sgns_pairs"] / window_s}

    def norms(tree):
        return {k: float(jnp.linalg.norm(v)) for k, v in tree.items()}

    def check(rounds) -> dict:
        out = walker.check(rounds)
        trained = [walks0] + [w for _, w in rounds]
        prog = {"losses": trainer.loss_history().astype(np.float64),
                "mu_norm": norms(trainer.opt_state.mu),
                "delta_norm": norms({k: trainer.params[k] - start[k]
                                     for k in start})}
        # free the trainer's tables before the reference builds its own
        trainer.params = trainer.opt_state = None
        trainer._losses = []
        start.clear()
        ref = sgns_reference.replay(trained, **sgns)
        if control:
            prog = sgns_reference.replay(trained, dtype_name="bfloat16",
                                         **sgns)
        out.update(sgns_reference.gaps(prog, ref))
        return out

    return Prepared(
        {**walker.info(), "sgns_seed": sgns["seed"]},
        lambda measure: timed_rounds(seconds, measure, one_round, finish),
        check, counts, rates)
