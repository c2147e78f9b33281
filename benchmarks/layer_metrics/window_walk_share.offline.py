"""How much of a full layer's page walk a window layer's is, over the
window's decode steps: 100 x ``kv_pages_window`` / (window layers x the
pages ONE full layer walked over the same steps, ``kv_pages_full`` / full
layers), from the scheduler's dispatch records (docs/OBSERVABILITY.md).
About 3 at 8k-token contexts under a window of 128 and pages of 128; 100
says the window no longer bounds the walk.  A count: it repeats exactly
over runs of one seed.  A program without the counters (no window layers,
the parent): None.

The reader also writes the pool's two shares (the scheduler report's
``window_cache`` block: gauges, which the harness's deltas over the window
cannot show) to standard error, one line."""

import json
import sys


def read(facts):
    cache = (facts.get("report") or {}).get("window_cache")
    if cache:
        print("window cache: " + json.dumps(cache), file=sys.stderr,
              flush=True)
    c = facts["counters"]
    full, win = c.get("kv_pages_full"), c.get("kv_pages_window")
    if not full or win is None or not cache:
        return None
    per_full_layer = full / cache["full_layers"]
    return 100.0 * win / (cache["window_layers"] * per_full_layer)
