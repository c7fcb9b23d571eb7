"""What decides ``correct`` and ``failed``, the same for every job kind.

A job kind's module gives ``LIMITS`` and ``readings(answer, ref)``: the
numbers compared for one answer. A run's check is the worst reading of
each number over every job it ran, held against its limit.
"""
from __future__ import annotations

from typing import Optional


def compare(job, answers: list, ref: dict) -> dict:
    """``{name: {"value": worst reading, "limit": limit}}``."""
    worst = dict.fromkeys(job.LIMITS, 0)
    for a in answers:
        for k, v in job.readings(a, ref).items():
            worst[k] = max(worst[k], v)
    return {k: {"value": worst[k], "limit": job.LIMITS[k]} for k in job.LIMITS}


def correct(compared: dict) -> bool:
    """A run is correct when every number is within its limit."""
    return all(c["value"] <= c["limit"] for c in compared.values())


def off_path(answer, program_names: list, path: list) -> Optional[str]:
    """Why a job did not run the cell's path, or None: another rung of
    the resilience ladder answered, a rung retried or shrank its budget,
    or the device programs launched are not the path's (a descent inside
    a job, such as the count under a peel, launches another program)."""
    rep = answer.report
    if rep.final_rung != rep.requested:
        return f"answered by {rep.final_rung}, asked {rep.requested}"
    for a in rep.attempts:
        if a.outcome != "ok" or a.retries or a.budget_shrinks:
            return f"attempt {a}"
    names = sorted(set(program_names))
    if names != sorted(path):
        return f"programs {names}, path {sorted(path)}"
    return None
