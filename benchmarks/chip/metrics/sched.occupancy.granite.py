"""Scheduler: mean share of the ``max_batch`` slots holding a request, per
decode step in the span; read as ``sched.occupancy.batch`` reads it."""

import pathlib

import cost

read = cost.load_module(
    pathlib.Path(__file__).with_name("sched.occupancy.batch.py")).read
