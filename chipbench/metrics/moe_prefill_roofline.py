"""Kernels: the held-experts layer's share of its roofline in the traced
prefill programs (``jit_prefill_step*``), as ``moe_decode_roofline``
reads it in the decode programs: the experts' bytes over the HBM
bandwidth or their FLOPs over the bf16 peak, whichever is larger, from
each prefill's own counts, over the device time of the expert ops inside
those programs."""

from chipbench import expert_ops

PROGRAM, STEP = "jit_prefill_step", "prefill"


def read(run):
    return expert_ops.roofline_share(run, PROGRAM, STEP)
