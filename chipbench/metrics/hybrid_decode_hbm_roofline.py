"""Whole decode step of a hybrid model against the HBM roofline: the
bytes each traced decode program (``jit_decode_step*``) needs
(``work_hybrid.decode_bytes``: the weights outside the experts, the
experts its tokens touched, the live K/V, the Mamba state read and
written) over the chip's HBM bandwidth, divided by the programs' device
time. A run without held-experts counts reads nothing."""

from chipbench import expert_ops, work_hybrid


def read(run):
    steps = expert_ops.steps(run, "jit_decode_step", "decode")
    if not steps:
        return None
    need = sum(work_hybrid.decode_bytes(run.config, t.decode_ctx, c[0])
               for t, progs, c in steps)
    took = sum(e.dur for _, progs, _ in steps for e in progs) * 1e-9
    return 100 * need / run.peaks.hbm_bytes_per_s / took
