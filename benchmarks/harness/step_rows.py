"""Readers of the program's step rows: the account that
``dlrover_tpu.observability.trace`` keeps of every interval between two
dispatches of ``ElasticTrainer.step`` (what the wall clock, the stepping
thread's CPU clock, the kernel's run queue, the collector and the
program's own spans say happened in it), kept in the program's process,
which is this one.

The trace comes after the window, so these rows are the one thing that
saw the window's own steps. The metrics read the rows the profiler had
no part in (``traced`` 0 and no ``edge``) but the process's first: that
one began at the warm-up step's dispatch and holds whatever the job did
before its window (``jobs/finetune_loop.py`` reads the live rows there).
What is left is the window's steps and the one or two after its last
whole cycle.

A row is *late* by the program's own rule (``trace.late_account``, the
function behind its ``late.<cause>`` counters), applied here with the
run's own median row where the program applies its running one.

Every reader returns None for a program that keeps no rows, and the
metric is then left out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmarks.harness import stats


def window_rows(rows: List[Dict]) -> List[Dict]:
    """The rows the metrics are taken over (module docstring)."""
    return [r for r in rows[1:] if not r["traced"] and not r["edge"]]


def numbers(rows: List[Dict], baseline, late_account) -> Optional[Dict]:
    """Every metric of this module from the process's ``rows``, with the
    program's ``trace.baseline`` and ``trace.late_account``. None
    without rows; a number that nothing measured is left out."""
    rows = window_rows(rows)
    if not rows:
        return None
    n = len(rows)
    intervals = [r["interval_s"] for r in rows]
    out = {
        "step_interval_ms": stats.median(intervals) * 1e3,
        "step_interval_mean_ms": sum(intervals) / n * 1e3,
        # the mean: where the thread's CPU clock ticks (by 10 ms on the
        # sandboxed kernel of the machine with the chip) a row reads 0 or
        # 10 and only the mean over the rows says what a step costs
        "step_host_cpu_ms": sum(r["cpu_s"] for r in rows) / n * 1e3,
        "gc_pause_ms": sum(sum(r["gc_s"]) for r in rows) / n * 1e3,
    }
    runq = [r["runq_s"] for r in rows if r["runq_s"] is not None]
    if runq:
        out["step_runq_ms"] = stats.median(runq) * 1e3
    quarter = n // 4
    if quarter >= 2:
        out["step_drift_pct"] = 100.0 * (
            stats.median(intervals[-quarter:])
            / stats.median(intervals[:quarter]) - 1.0)
    late = late_account(rows, baseline(rows))
    out["late_steps_pct"] = 100.0 * late.pop("n") / n
    for cause, seconds in late.items():
        if cause != "runq" or runq:
            out[f"late_{cause}_ms"] = seconds / n * 1e3
    out["late_ms_per_step"] = sum(late.values()) / n * 1e3
    return out


def numbers_of(ctx) -> Optional[Dict]:
    """This process's numbers, computed once and kept on ``ctx``."""
    if not hasattr(ctx, "step_row_numbers"):
        from dlrover_tpu.observability import trace

        rows = getattr(trace, "step_rows", list)()
        ctx.step_row_numbers = numbers(
            rows, trace.baseline, trace.late_account) if rows else None
    return ctx.step_row_numbers


def read(spec, ctx) -> Optional[float]:
    """The number ``spec["number"]`` of ``numbers``."""
    found = numbers_of(ctx)
    return None if found is None else found.get(spec["number"])
