"""Model FLOPs of the tokens the device computed in the window (prompt
tokens not served from cache, plus generated tokens; matmuls plus causal
attention, from the traffic's own lengths) over window seconds x bf16 peak."""
from _shared import mfu_percent as read  # noqa: F401
