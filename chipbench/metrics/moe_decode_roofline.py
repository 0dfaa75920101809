"""Kernels: the held-experts layer's share of its roofline in the traced
decode programs (``jit_decode_step*``). Per step, the roofline time is the
larger of the bytes the experts need over the HBM bandwidth and their
FLOPs over the bf16 peak, from the step's own counts
(``work_hybrid.moe_work``); it is divided by the device time of the
expert ops (``work_hybrid.EXPERT_OPS``) inside those programs. A program
without such ops, or a run without counts, reads nothing."""

from chipbench import expert_ops

PROGRAM, STEP = "jit_decode_step", "decode"


def read(run):
    return expert_ops.roofline_share(run, PROGRAM, STEP)
