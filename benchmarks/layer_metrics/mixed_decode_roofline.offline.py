"""The decode kernel over a two-kind cache against the memory roofline: the
K/V bytes that the window's decode steps had to read, step by step from the
requests' lengths (generated token j of a request reads prompt + j cached
positions on a full layer and min(prompt + j, window) on a window layer:
the family's ``kv_read_bytes``), over HBM bandwidth, divided by the decode
kernel's device time in the trace.  Memory bound.
``paged_decode_roofline.offline`` hands the family one sum of live tokens,
which a window layer's bytes are no function of; this reader walks the
steps.  A family without ``kv_read_bytes`` (no window layers), or a trace
without the kernel: None."""
from _shared import kernel_seconds

KERNEL = ("paged_decode", "paged-decode", "decode_fused", "ragged_decode")


def read(facts):
    secs = kernel_seconds(facts, *KERNEL)
    fl = facts["flops"]
    if not secs or not facts.get("peaks") or not hasattr(fl, "kv_read_bytes"):
        return None
    m = facts["model"]
    nbytes = sum(fl.kv_read_bytes(m, r["prompt"] + j)
                 for r in facts["window"]["requests"]
                 for j in range(1, r["generated"] + 1))
    return 100.0 * nbytes / facts["peaks"]["hbm_bytes_per_s"] / secs
