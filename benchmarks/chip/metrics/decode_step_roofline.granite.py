"""Decode step's share of its roofline, read as ``decode_step_roofline``
reads it; the least time comes from ``costs/granitemoehybrid.py`` (every
weight once, each token's float32 SSM state and conv windows read and
written in the 36 Mamba layers, the KV cache of the 4 attention layers at
actual lengths)."""

import pathlib

import cost

read = cost.load_module(
    pathlib.Path(__file__).with_name("decode_step_roofline.py")).read
