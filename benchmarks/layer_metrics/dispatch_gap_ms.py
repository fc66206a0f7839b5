"""Median device-idle gap between consecutive executions of the step
program, from the device trace."""

import statistics

import tracered as tr


def read(run: dict):
    runs = tr.program_runs(run["trace"], run["step_program"])
    gaps = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    if not gaps:
        return None
    return statistics.median(gaps) / 1e6
