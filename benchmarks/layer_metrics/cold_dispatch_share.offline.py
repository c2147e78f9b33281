"""Share of the scheduler's run time spent in dispatches of a key that had
never run (the dispatch call blocks on the compile): cold_seconds /
run_seconds, both the scheduler's counters over the window.  0 in a window
that compiles nothing.  None on a tree without the counter (before PR 25,
or LMRS_ANATOMY=0)."""


def read(facts):
    c = facts["counters"]
    if "cold_seconds" not in c or not c.get("run_seconds"):
        return None
    return 100.0 * c["cold_seconds"] / c["run_seconds"]
