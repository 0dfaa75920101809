"""The served-token check of the serving cells that name their reference
module: ``drivers.serving.served_gap``'s comparison, with the reference
and its weights given rather than fixed to the dense decoder drawn from
the seed.

A row is a prompt followed by its served tokens but the last; the
reference's logits at the positions that produced each served token give
the gap of the served token's logit below the reference's best, in the
reference's standard deviations (``ref.gaps``). With ``control`` the
reference also runs at its low precision (``lowp=True``), and the gap of
the token that control puts first is read the same way.
"""

from __future__ import annotations

import numpy as np


def served_gap(ref, config: dict, flat: dict, sample, prompts, served,
               control: bool = False, **kw) -> tuple:
    """``(widest gap, served tokens compared, control's widest gap or
    None)`` over every served token of ``sample`` (records with ``rid``),
    with ``prompts`` and ``served`` keyed by ``rid``; ``flat`` is the
    weights (``{path: array}``) ``ref.logits`` reads, ``kw`` go to it."""
    S = max(len(prompts[r.rid]) + len(served[r.rid]) for r in sample)
    K = max(len(served[r.rid]) for r in sample)
    toks = np.zeros((len(sample), S), np.int32)
    at = np.zeros((len(sample), K), np.int32)
    out = np.zeros((len(sample), K), np.int32)
    real = np.zeros((len(sample), K), bool)
    for i, r in enumerate(sample):
        p, s = prompts[r.rid], served[r.rid]
        toks[i, :len(p) + len(s) - 1] = [*p, *s[:-1]]
        at[i, :len(s)] = len(p) - 1 + np.arange(len(s))
        out[i, :len(s)] = s
        real[i, :len(s)] = True
    base = ref.logits(flat, config, toks, at, **kw)
    gap = ref.gaps(base, out)[:len(sample)][real]
    ctl = None
    if control:
        low = ref.argmax(ref.logits(flat, config, toks, at, lowp=True, **kw))
        ctl = float(ref.gaps(base, low)[:len(sample)][real].max())
    return float(gap.max()), int(real.sum()), ctl
