"""Faults planted in the timed path underneath the harness, for
``test_faults.py`` (tiny, on the CPU) and ``run_control.py`` (the cell's
size, on the chip). Each takes pytest's ``monkeypatch`` or anything with
its ``setattr``."""
import dataclasses

import jax.numpy as jnp


def token_altered(mp):
    """Every walk's sixth step repeats its fifth: a step that is no edge."""
    from repro.engine import WalkEngine
    run = WalkEngine.run

    def broken(self, *a, **kw):
        res = run(self, *a, **kw)
        walks = res.walks.copy()
        walks[:, 5] = walks[:, 4]
        return dataclasses.replace(res, walks=walks)
    mp.setattr(WalkEngine, "run", broken)


def walks_half_left_out(mp):
    """Half of each round's walks never come back."""
    from repro.engine import WalkEngine
    run = WalkEngine.run

    def broken(self, *a, **kw):
        res = run(self, *a, **kw)
        return dataclasses.replace(res, walks=res.walks[
            :res.walks.shape[0] // 2])
    mp.setattr(WalkEngine, "run", broken)


def state_unchanged(mp):
    """Each training epoch returns the tables and Adam state it was given."""
    from repro.train import stream

    def broken(params, opt_state, c, x, valid, perm2d, *a, **kw):
        return params, opt_state, jnp.full((perm2d.shape[0],),
                                           6 * jnp.log(2.0))
    mp.setattr(stream, "_train_epoch", broken)


def batch_half_left_out(mp):
    """Every other pair of each batch is masked out; the loss and the
    gradient are the mean over the rest."""
    from repro.train import stream
    epoch = stream._train_epoch

    def broken(params, opt_state, c, x, valid, *a, **kw):
        half = valid & (jnp.arange(valid.shape[0]) % 2 == 0)
        return epoch(params, opt_state, c, x, half, *a, **kw)
    mp.setattr(stream, "_train_epoch", broken)


def alias_frozen(mp):
    """The negatives' alias table stays as the first round built it: later
    rounds draw from stale counts."""
    from repro.train.stream import StreamingSGNSTrainer
    refresh = StreamingSGNSTrainer._alias_refresh

    def broken(self, walks):
        if not hasattr(self, "_frozen_alias"):
            self._frozen_alias = refresh(self, walks)
        return self._frozen_alias
    mp.setattr(StreamingSGNSTrainer, "_alias_refresh", broken)


def later_rounds_skipped(mp):
    """Every round after the first is taken and not trained."""
    from repro.train.stream import StreamingSGNSTrainer
    consume = StreamingSGNSTrainer.consume

    def broken(self, walks):
        if self._round == 0:
            return consume(self, walks)
        self._round += 1
    mp.setattr(StreamingSGNSTrainer, "consume", broken)


WALK = {"token_altered": token_altered,
        "walks_half_left_out": walks_half_left_out}
TRAIN = {"state_unchanged": state_unchanged,
         "batch_half_left_out": batch_half_left_out,
         "alias_frozen": alias_frozen,
         "later_rounds_skipped": later_rounds_skipped}
