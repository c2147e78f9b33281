"""Reduce seconds over job seconds, from the stage_times of the window's jobs."""


def read(facts):
    jobs = facts["window"].get("jobs") or []
    total = sum(j["stage_times"].get("total", 0.0) for j in jobs)
    if total <= 0:
        return None
    return 100.0 * sum(j["stage_times"].get("reduce", 0.0) for j in jobs) / total
