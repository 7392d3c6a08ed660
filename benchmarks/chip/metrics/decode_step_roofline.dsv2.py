"""Decode step's share of its roofline, read as ``decode_step_roofline``
reads it; the least time comes from ``costs/deepseek_v2.py`` (absorbed
MLA, the latent cache at actual lengths, the routed experts a step is
expected to hit)."""

import pathlib

import cost

read = cost.load_module(
    pathlib.Path(__file__).with_name("decode_step_roofline.py")).read
