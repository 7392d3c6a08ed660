"""Whole step: the operations the model needs for every token the engine
computed in the traced span, over the span times the chip's peak bf16
rate; read as ``step.mfu.batch`` reads it."""

import pathlib

import cost

read = cost.load_module(
    pathlib.Path(__file__).with_name("step.mfu.batch.py")).read
