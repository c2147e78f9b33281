"""Flash prefill kernel against the compute roofline: causal-attention FLOPs
of the prompt tokens the window computed fresh, over the bf16 peak, divided
by the kernel's device time in the trace.  Compute bound."""
from _shared import cached_split, kernel_seconds

KERNEL = ("flash",)


def read(facts):
    secs = kernel_seconds(facts, *KERNEL)
    if not secs or not facts.get("peaks"):
        return None
    m, fl = facts["model"], facts["flops"]
    work = sum(fl.attn_flops(m, c, 0) for c, k, _ in cached_split(facts)
               if k == 0)
    if not work:
        return None
    return 100.0 * work / facts["peaks"]["bf16_flops"] / secs
