"""Decode step (``decode_step``, jitted in the engine): median device time
of one execution of the step program in the traced window; read as
``decode.step_device_ms.batch`` reads it."""

import pathlib

import cost

read = cost.load_module(
    pathlib.Path(__file__).with_name("decode.step_device_ms.batch.py")).read
