"""Device: share of the traced window in which no operation ran; read as
``device.idle_share.batch`` reads it."""

import pathlib

import cost

read = cost.load_module(
    pathlib.Path(__file__).with_name("device.idle_share.batch.py")).read
