"""Global optimization: particle-swarm search over the RAV (Algorithm 1).

Each particle is a 5-dim position [SP, Batch, dsp_frac, bram_frac, bw_frac];
fitness is the (scalarized) objective returned by the local optimizers
(:func:`repro.core.local_opt.evaluate_rav`). The swarm itself is
:class:`repro.core.search.PSOSearcher`, one of the two engines behind the
ask/tell protocol; :func:`optimize` runs it on a caller's fitness.
"""
from __future__ import annotations

from typing import Callable, Sequence

import numpy as np

from .local_opt import RAV
from .search import (PSOConfig, PSOSearcher, SearchResult, SearchSpace,
                     run_search)

#: Historical name: every engine now returns this shared result type.
PSOResult = SearchResult


def _clip(pos: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    return np.clip(pos, lo, hi)


def _to_rav(pos: np.ndarray) -> RAV:
    return RAV(sp=int(round(pos[0])), batch=max(1, int(round(pos[1]))),
               dsp_frac=float(pos[2]), bram_frac=float(pos[3]),
               bw_frac=float(pos[4]))


def optimize(fitness_fn: Callable[[RAV], float] | None = None, *,
             sp_max: int, batch_max: int = 1,
             cfg: PSOConfig | None = None,
             batch_fitness_fn: Callable[[Sequence[RAV]], Sequence[float]] | None = None,
             ) -> PSOResult:
    """Algorithm 1. Fitness must be deterministic (results are memoized on the
    rounded RAV so repeated positions are free). Exactly one of ``fitness_fn``
    (scalar, one RAV per call) or ``batch_fitness_fn`` (whole population per
    call) is required; with both given the batch hook wins.
    """
    if fitness_fn is None and batch_fitness_fn is None:
        raise TypeError("optimize() needs fitness_fn or batch_fitness_fn")
    space = SearchSpace(sp_max=sp_max, batch_max=batch_max)
    searcher = PSOSearcher(space, cfg or PSOConfig())
    return run_search(searcher, fitness_fn=fitness_fn,
                      batch_fitness_fn=batch_fitness_fn)
