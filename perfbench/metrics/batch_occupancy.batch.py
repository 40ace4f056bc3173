"""Serving engine: mean over the window's steps of occupied decode slots /
max_batch, in percent, read after each step."""


def read(run):
    steps = run.get("engine_steps")
    if not steps:
        return None
    return 100.0 * sum(s[2] for s in steps) / len(steps) / run["max_batch"]
