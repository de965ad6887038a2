"""Traffic kind ``walk_rounds``: rounds of walkers through the program's
``WalkEngine.run(starts=...)``, back to back, with no training.

Set-up loads the configuration's graph, has the program build its device
layout (``graph_build_s``), and warms up one whole round. The window then
runs whole rounds until ``seconds`` have passed; it closes when the last
round that started inside it returns its walks to the host.
"""
from __future__ import annotations

from chipbench.core.walker import Prepared, Walker, timed_rounds

GROUPS = ("walk",)


def prepare(cell, seed: int, seconds: float, span, *, control: bool = False):
    """Set the cell up and warm it up. Returns a ``Prepared`` whose
    ``window()`` runs the measured rounds, ``check(rounds)`` compares them
    with the references and ``counts(rounds)`` counts their work. ``span``
    names host spans. ``control`` walks with q = 1 (``Walker``)."""
    walker = Walker(cell.config, cell.traffic["walkers_per_round"], seed,
                    span, control=control)
    with span("setup.warmup"):
        walker.run(0)

    def one_round(r):
        with span("walk.run"):
            return walker.run(r)

    def rates(counts, window_s):
        return {"walk_steps_per_s": counts["walk_steps"] / window_s}

    return Prepared(
        walker.info(),
        lambda measure: timed_rounds(seconds, measure, one_round),
        walker.check, walker.counts, rates)
