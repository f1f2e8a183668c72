"""Resize actions of the elastic policy (``Table.policy_stats()``: splits
plus merges) per 1,000 operations issued in the window."""


def read(run):
    if run.policy is None or run.ops <= 0:
        return None
    return (run.policy["splits"] + run.policy["merges"]) * 1000.0 / run.ops
