"""round_mfu (%): the FLOPs the traced rounds required, over the traced
window's length times the chips times the chip's bf16 peak.

Required FLOPs of a round: every client's kappa_u mini-batch steps of B
samples at the model's training FLOPs per sample, plus the evaluation set at
its forward FLOPs (``flops_per_sample`` beside the configuration). The kappa_u
are the round's own resource decisions, solved by the reference
(``yardstick/resource.py``) from the round's channel draw; the masked steps
past kappa_u that the program also runs do not count. Float32 matrix products
at default precision run as bf16 passes on a TPU, so the bf16 peak is the
one to hold them against."""


def read(t):
    span_s = (t.hi - t.lo) / 1e9
    if span_s <= 0 or t.flops <= 0:
        return None
    return 100.0 * t.flops / (span_s * t.chips * t.peaks["bf16_flops"])
