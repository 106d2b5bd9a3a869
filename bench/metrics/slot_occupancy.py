"""Scheduler: share of the engine's slots holding a request after each
step, mean over the window's steps, in percent."""


def read(red, rec, ctx):
    steps = rec["all_steps"]
    if not steps:
        return None
    return 100.0 * sum(s["occupancy"] for s in steps) / len(steps)
