"""Traffic generation from a workload's parameters and the seed.

One general generator: an open loop of requests with exponential gaps
between arrivals on the wall clock and lognormal prompt and output lengths (the length draws of
``serving/traces.py``, with arrivals in seconds instead of ticks).

Every seed gets the same requests in another order. The ``n`` prompt
lengths are the lognormal's quantiles at ``(i + 1/2) / n``, the output
lengths likewise, and the ``n`` gaps between arrivals the exponential's;
the workload's fixed ``order_seed`` shuffles each into one sequence of
requests (a gap, a prompt, an output), and the run's seed rotates that
sequence, so that another request comes first and the window wraps round
at another place. The token ids are drawn from the seed. So every seed
offers the same work with the same bursts, and two seeds differ only in
where the sequence starts: a seed that shuffled the gaps anew would make
some windows bursty enough to fill every slot and others not, and the
tail of time to first token would follow the seed rather than the
program.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due: float          # seconds after the window opens
    prompt: tuple
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the quantiles of a lognormal with ``median`` and
    log-space ``sigma``, rounded and clipped to ``[min, max]``."""
    z = np.array([statistics.NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x).astype(int), spec["min"], spec["max"])


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    return -np.log1p(-_quantiles(n)) / rate


def open_loop(spec: dict, seconds: float, seed: int, vocab: int) -> list:
    """The requests due in a window of ``seconds``: ``rate_per_s * seconds``
    of them, the first due at once."""
    n = max(1, round(spec["rate_per_s"] * seconds))
    order = np.random.default_rng(spec["order_seed"])
    prompts = order.permutation(lognormal_lengths(n, spec["prompt"]))
    outs = order.permutation(lognormal_lengths(n, spec["output"]))
    gaps = order.permutation(exponential_gaps(n, spec["rate_per_s"]))
    turn = seed % n
    prompts, outs, gaps = (np.roll(x, -turn) for x in (prompts, outs, gaps))
    rng = np.random.default_rng(seed)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due *= min(1.0, 0.999 * seconds / max(due[-1], 1e-9))
    return [Arrival(i, float(due[i]),
                    tuple(int(t) for t in rng.integers(1, vocab, prompts[i])),
                    int(outs[i]))
            for i in range(n)]
