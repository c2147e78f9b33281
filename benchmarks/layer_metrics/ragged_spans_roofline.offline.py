"""Ragged span kernel against the compute roofline: attention FLOPs of the
prompt tokens that were computed behind a cached prefix (each attends the
cached tokens and, causally, its own span), over the bf16 peak, divided by
the kernel's device time in the trace.  Compute bound at these span lengths
(some 1.5k query tokens a row)."""
from _shared import cached_split, kernel_seconds

KERNEL = ("ragged_spans",)


def read(facts):
    secs = kernel_seconds(facts, *KERNEL)
    if not secs or not facts.get("peaks"):
        return None
    m, fl = facts["model"], facts["flops"]
    work = sum(fl.attn_flops(m, c, k) for c, k, _ in cached_split(facts)
               if k > 0)
    if not work:
        return None
    return 100.0 * work / facts["peaks"]["bf16_flops"] / secs
